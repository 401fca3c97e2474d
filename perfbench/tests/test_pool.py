"""The committed hash-to-G2 pool against the plain reference."""

import random

from perfbench import generate
from perfbench.data.make_h2g2_pool import point_ints, pool_message
from perfbench.ref.constants import DST
from perfbench.ref.curves import _B2, Point
from perfbench.ref.fields import Fp2
from perfbench.ref.hash_to_curve import hash_to_g2


def test_pool_entries_are_the_reference_hash_to_g2():
    msgs, pts = generate.load_pool()
    assert len(msgs) == len(pts) == 1024
    for i in [0, len(msgs) - 1] + random.Random(7).sample(range(1, len(msgs) - 1), 2):
        assert msgs[i] == pool_message(i)
        assert list(pts[i]) == point_ints(hash_to_g2(msgs[i], DST))


def test_pool_points_are_on_the_curve():
    _, pts = generate.load_pool()
    for c in pts[:64]:
        assert Point(Fp2.from_ints(c[0], c[1]), Fp2.from_ints(c[2], c[3]), False, _B2).is_on_curve()
