"""Write the benchmark's pool of messages and their hash-to-G2 points.

    python3 perfbench/data/make_h2g2_pool.py [--count 1024]

Each message is a 32-byte signing root, sha256(b"perfbench h2g2 pool" || i),
and its point is `hash_to_g2(message, DST)` of the benchmark's own copy of
the plain reference (perfbench/ref), in affine coordinates. Traffic signs
with these points, so no run pays the pure-Python hash-to-curve (about
0.1 s a message). `perfbench/tests/test_pool.py` recomputes entries.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
POOL = HERE / "h2g2_pool.json"


def pool_message(i: int) -> bytes:
    return hashlib.sha256(b"perfbench h2g2 pool" + i.to_bytes(4, "big")).digest()


def point_ints(pt) -> list[int]:
    """(x.c0, x.c1, y.c0, y.c1) of an affine G2 point."""
    return [pt.x.c0.n, pt.x.c1.n, pt.y.c0.n, pt.y.c1.n]


def main(argv=None) -> int:
    sys.path.insert(0, str(HERE.parents[1]))
    from perfbench.ref.constants import DST
    from perfbench.ref.hash_to_curve import hash_to_g2

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--count", type=int, default=1024)
    args = ap.parse_args(argv)
    entries = []
    for i in range(args.count):
        msg = pool_message(i)
        entries.append([msg.hex()] + [format(c, "x") for c in point_ints(hash_to_g2(msg, DST))])
    POOL.write_text(json.dumps({"dst": DST.decode(), "entries": entries}, indent=0) + "\n")
    print(f"wrote {len(entries)} entries to {POOL}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
