"""Config-3 MFU frontier: the same BERT step at optimizer-amortizing settings.

The canonical config (batch 64/chip, ``bench_bert.py``) measured MFU 0.591 on
the real chip; the step-time roofline says the largest per-sample non-matmul
cost at that batch is the f32 AdamW state traffic (7 passes over 109.5 M
params ~ 3.1 GB/step ~ 3.7 ms against 21.3 ms of ideal matmul), which scales
as 1/batch. This bench measures the SAME model/step at batch 256 with longer
``lax.scan`` bodies (steps_per_call 30) — the frontier that tells us how much
of the 0.59 -> 1.0 gap is batch-amortizable overhead vs real inefficiency.

Emits ``bert_base_sst2_mfu_frontier`` so the canonical number stays separate.
"""

import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# must be set before bench_bert is imported (it reads env at module load).
# STEPS is chosen so total batches (STEPS + 10) divide evenly into
# steps_per_call groups: a ragged tail scan would RECOMPILE inside the timed
# window (driver.py compiles once per distinct scan length) and deflate the
# frontier number with minutes of compile.
os.environ.setdefault("BENCH_BERT_BATCH", "256")
os.environ.setdefault("BENCH_BERT_STEPS_PER_CALL", "30")
os.environ.setdefault("BENCH_BERT_STEPS", "80")  # 90 batches -> [30, 30, 30]
os.environ.setdefault("BENCH_BERT_METRIC", "bert_base_sst2_mfu_frontier")
# bf16 first moment halves one of AdamW's f32 state passes (frontier-only;
# the canonical bench_bert keeps full-f32 optimizer state)
os.environ.setdefault("BENCH_BERT_MU_DTYPE", "bfloat16")

from benchmarks import bench_bert  # noqa: E402

if __name__ == "__main__":
    bench_bert.main()
