"""Run the BLS conformance matrix against the jax backend on the attached
accelerator (tests/ force the CPU mesh; this script runs on the real chip)."""

import pathlib
import sys

_ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_ROOT))


def main() -> None:
    from lighthouse_tpu.conformance import generate_bls_cases, run_case
    from lighthouse_tpu.crypto import bls

    backend = bls.backend(sys.argv[1] if len(sys.argv) > 1 else "jax")
    cases = generate_bls_cases()
    failed = 0
    for case in cases:
        try:
            run_case(case, backend)
        except AssertionError as e:
            failed += 1
            print(f"FAIL {case.case_type}/{case.name}: {e}")
    print(f"{len(cases) - failed}/{len(cases)} conformance cases passed")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
