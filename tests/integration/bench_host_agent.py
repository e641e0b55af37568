"""Worker entry for the HOST-bench smoke test.

kfrun passes the worker command through argparse.REMAINDER, which chokes
on option-like tokens (`python -m ...`, `--method ...`), so the smoke
launches this script and feeds the bench flags through KF_BENCH_* envs.
"""

import os
import sys


def main() -> None:
    argv = [
        "kungfu_tpu.benchmarks",
        "--method", "HOST",
        "--model", os.environ.get("KF_BENCH_MODEL", "tiny"),
        "--iters", os.environ.get("KF_BENCH_ITERS", "2"),
    ]
    algo = os.environ.get("KF_BENCH_ALGO", "")
    if algo:
        argv += ["--algo", algo]
    wire = os.environ.get("KF_BENCH_WIRE", "")
    if wire:
        argv += ["--wire", wire]
    if os.environ.get("KF_BENCH_ASYNC", ""):
        argv += ["--async"]
    if os.environ.get("KF_BENCH_PASSES", ""):
        argv += ["--passes", os.environ["KF_BENCH_PASSES"]]
    sys.argv = argv
    from kungfu_tpu.benchmarks.__main__ import main as bench_main

    bench_main()


if __name__ == "__main__":
    main()
