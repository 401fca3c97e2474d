"""The benchmark's own copy of the plain BLS12-381 reference.

Copied from lighthouse_tpu/crypto/bls/ref (fields, curves, hash_to_curve,
pairing) and crypto/bls/constants.py, so that the yardstick imports nothing
of the program under test. `verify.py` holds the checks the benchmark runs.
"""
