"""Config-5 MFU frontier: the same ViT step at optimizer-amortizing settings.

The canonical config (batch 64/chip, ``bench_vit.py``) last measured a
device-resident MFU of 0.56 (round 1); as with BERT the f32 AdamW state traffic
(~3.0 GB/step over 86 M params) and short scan bodies are the batch-amortizable
costs. Batch 256 + steps_per_call 20 measures the frontier; the
``device_resident_mfu`` field is the number the roofline argument needs (the
prefetch path additionally includes the host->device link).

Emits ``vit_mfu_frontier`` so the canonical number stays separate.
"""

import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# must be set before bench_vit is imported (it reads env at module load)
os.environ.setdefault("BENCH_VIT_BATCH", "256")
os.environ.setdefault("BENCH_VIT_STEPS_PER_CALL", "20")
os.environ.setdefault("BENCH_VIT_METRIC", "vit_mfu_frontier")

from benchmarks import bench_vit  # noqa: E402

if __name__ == "__main__":
    bench_vit.main()
