"""Lower + compile one (arch x shape) cell on the production mesh and
print its memory/cost/roofline terms — the per-cell view of the
multi-pod dry-run.

    PYTHONPATH=src python examples/dryrun_cell.py --arch qwen2-moe-a2.7b \
        --shape train_4k [--multi-pod]

NOTE: must be a fresh process (forces 512 host devices).
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")

import argparse   # noqa: E402
import sys        # noqa: E402

sys.path.insert(0, "src")

from repro.launch import compile_cache     # noqa: E402
from repro.launch.dryrun import run_cell    # noqa: E402


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-moe-a2.7b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    rec = run_cell(args.arch, args.shape, args.multi_pod)
    for k, v in rec.items():
        if k == "trace":
            continue
        print(f"{k:>32s}: {v}")
    assert rec["status"] in ("ok", "skipped"), rec.get("error")


if __name__ == "__main__":
    main()
