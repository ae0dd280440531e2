"""Versioned on-disk tile cache: the persisted half of the autotuner.

Layout — one JSON file per location, schema:

    {"version": 1,
     "entries": {
       "<family>|<backend>|<bucket>": {
         "block": [2048, 512, 512],
         "us": 15431.0,             # measured winner time (audit trail)
         "bound_us": 3.8,           # its roofline lower bound
         "n_candidates": 36, "n_pruned": 29,
         "jax": "0.4.37", "source": "measured"
       }, ...}}

Lookup order (first hit wins):

  1. the user cache — `$REPRO_TUNE_CACHE_DIR/tiles.json` (written by
     `python -m repro.tune`), read only when that variable is set;
  2. the in-repo fallback `src/repro/tune/defaults.json`, committed with
     tuned entries for the CPU CI shapes so `block="auto"` hits on fresh
     checkouts and CI runners.

Shapes are BUCKETED before keying: each dim rounds up to the next power
of two, so nearby problem sizes share one tuned tile (the kernels clamp
tiles to actual dims, so an entry tuned at the bucket ceiling stays
valid for every shape inside the bucket).

A `version` mismatch invalidates a file wholesale — entries are never
reinterpreted across schema changes; `store()` always writes the current
version (dropping stale-version entries on the first write).
"""
from __future__ import annotations

import json
import os
from typing import Optional

CACHE_VERSION = 1
CACHE_ENV = "REPRO_TUNE_CACHE_DIR"
CACHE_FILENAME = "tiles.json"

# (abspath, mtime_ns) -> entries dict; re-read only when the file changes
_LOAD_MEMO: dict[tuple[str, int], dict] = {}


def _pow2ceil(v: int) -> int:
    return 1 if v <= 1 else 1 << (int(v) - 1).bit_length()


def bucket_shape(shape: tuple[int, ...]) -> tuple[int, ...]:
    """Power-of-two ceiling per dim — the cache's shape equivalence class."""
    return tuple(_pow2ceil(int(s)) for s in shape)


def cache_key(family: str, shape: tuple[int, ...], backend: str) -> str:
    bucket = "x".join(str(s) for s in bucket_shape(shape))
    return f"{family}|{backend}|{bucket}"


def user_cache_path() -> Optional[str]:
    """`$REPRO_TUNE_CACHE_DIR/tiles.json`, or None when it is unset: a
    run then depends on no tile file outside the checkout."""
    base = os.environ.get(CACHE_ENV)
    return os.path.join(base, CACHE_FILENAME) if base else None


def defaults_path() -> str:
    """The committed in-repo fallback (CPU CI shapes)."""
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "defaults.json")


def _load_entries(path: str) -> dict:
    """Entries of one cache file; {} when absent or version-mismatched."""
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError:
        return {}
    memo_key = (os.path.abspath(path), mtime)
    if memo_key in _LOAD_MEMO:
        return _LOAD_MEMO[memo_key]
    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, json.JSONDecodeError):
        payload = {}
    entries = payload.get("entries", {}) \
        if payload.get("version") == CACHE_VERSION else {}
    _LOAD_MEMO[memo_key] = entries
    return entries


class TileCache:
    """One cache file (user cache, repo defaults, or a test tmpdir)."""

    def __init__(self, path: str):
        self.path = path

    def lookup(self, family: str, shape: tuple[int, ...],
               backend: str) -> Optional[dict]:
        return _load_entries(self.path).get(
            cache_key(family, shape, backend))

    def store(self, family: str, shape: tuple[int, ...], backend: str,
              block, meta: Optional[dict] = None) -> dict:
        """Merge one winner into the file (read-modify-write).

        Stale-version files are dropped wholesale on the first store —
        old-schema entries are never carried forward.
        """
        entries = dict(_load_entries(self.path))
        entry = {"block": [int(b) for b in
                           (block if isinstance(block, (tuple, list))
                            else (block,))]}
        entry.update(meta or {})
        entries[cache_key(family, shape, backend)] = entry
        os.makedirs(os.path.dirname(os.path.abspath(self.path)),
                    exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"version": CACHE_VERSION, "entries": entries},
                      f, indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp, self.path)
        return entry


def lookup_entry(family: str, shape: tuple[int, ...],
                 backend: Optional[str] = None) -> Optional[dict]:
    """User cache first, then the committed repo defaults."""
    if backend is None:
        from repro.kernels import common as kcommon
        backend = kcommon.backend()
    for path in (user_cache_path(), defaults_path()):
        if path is None:
            continue
        ent = _load_entries(path).get(cache_key(family, shape, backend))
        if ent is not None:
            return ent
    return None


def lookup_block(family: str, shape: tuple[int, ...],
                 backend: Optional[str] = None
                 ) -> Optional[tuple[int, ...]]:
    """The tuned tile for `(family, shape-bucket, backend)`, or None."""
    ent = lookup_entry(family, shape, backend)
    if ent is None or not ent.get("block"):
        return None
    return tuple(int(b) for b in ent["block"])
