"""Record the small device trace that `test_chipbench_trace.py` reduces.

    python3 chipbench/tests/record_trace.py [--out DIR]

Runs two short §IV sessions (UncodedFL, then CodedFL at c = 2016, 5
epochs each) under the JAX profiler on a TPU, with the benchmark's own
host spans around each public call, and copies the `.xplane.pb` it wrote
to `chipbench/tests/data/sec4_small.xplane.pb`, keeping only the planes
`trace_reduce.py` reads (the TPU and the host CPU: `/host:metadata` alone
is some 6 MB).  It also prints every plane and line of the trace with its
event count and the most frequent event names, which is how the names in
`trace_reduce.py` were chosen.

    python3 chipbench/tests/record_trace.py --trim FILE   # trim a file
"""
from __future__ import annotations

import argparse
import collections
import glob
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import CodedFL, Session, TrainData, UncodedFL  # noqa: E402
from repro.sim.network import paper_fleet  # noqa: E402


KEEP = ("/device:TPU:0", "/host:CPU")


def _varint(buf: bytes, i: int):
    value, shift = 0, 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _plane_name(plane: bytes) -> str:
    """Field 2 (name) of an XPlane message."""
    j = 0
    while j < len(plane):
        key, j = _varint(plane, j)
        if key & 7 == 0:
            _, j = _varint(plane, j)
        elif key & 7 == 2:
            n, j = _varint(plane, j)
            if key >> 3 == 2:
                return plane[j:j + n].decode()
            j += n
        else:
            j += 8 if key & 7 == 1 else 4
    return ""


def trim(path: str, keep=KEEP) -> None:
    """Rewrite an XSpace keeping only the planes named in `keep` (field 1
    of XSpace holds the planes; every other field is kept as it is)."""
    with open(path, "rb") as f:
        buf = f.read()
    out, i = bytearray(), 0
    while i < len(buf):
        start = i
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 2:
            n, i = _varint(buf, i)
            body = buf[i:i + n]
            i += n
            if key >> 3 == 1 and _plane_name(body) not in keep:
                continue
        elif wire == 0:
            _, i = _varint(buf, i)
        else:
            i += 8 if wire == 1 else 4
        out += buf[start:i]
    with open(path, "wb") as f:
        f.write(bytes(out))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(ROOT, "chipbench", "out",
                                                  "record_trace"))
    ap.add_argument("--trim", help="only trim this recorded file")
    args = ap.parse_args()
    if args.trim:
        trim(args.trim)
        return 0
    if jax.devices()[0].platform != "tpu":
        print("no TPU: this records a device trace", file=sys.stderr)
        return 2
    data = TrainData.linreg(jax.random.PRNGKey(0), n=24, ell=300, d=500)
    fleet = paper_fleet(0.2, 0.2, seed=0)
    sessions = [Session(strategy=UncodedFL(), fleet=fleet, lr=0.0085,
                        epochs=5),
                Session(strategy=CodedFL(key=jax.random.PRNGKey(1),
                                         fixed_c=2016),
                        fleet=fleet, lr=0.0085, epochs=5)]

    def one(sess, i):
        with jax.profiler.TraceAnnotation("session"):
            with jax.profiler.TraceAnnotation("plan"):
                state = sess.plan(data)
                jax.block_until_ready(jax.tree.leaves(vars(state)))
            with jax.profiler.TraceAnnotation("run"):
                sess.run(data, rng=np.random.default_rng(i), state=state)

    for i, s in enumerate(sessions):  # warm: compile outside the trace
        one(s, i)
    shutil.rmtree(args.out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(args.out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        for i, s in enumerate(sessions):
            one(s, 10 + i)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(args.out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            lo = min((e.start_ns for e in evs), default=0)
            hi = max((e.start_ns + e.duration_ns for e in evs), default=0)
            print(f"  LINE {line.name!r} events={len(evs)} span=[{lo}, {hi}]"
                  f" top={names.most_common(12)}")
    dest = os.path.join(HERE, "data", "sec4_small.xplane.pb")
    shutil.copy(path, dest)
    trim(dest)
    print(f"wrote {dest} ({os.path.getsize(dest)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
