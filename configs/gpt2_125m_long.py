"""GPT-2 125M at 8k context: the streamed flash backward on one chip.

Above ``STREAM_SEQ_THRESHOLD`` (4096) the flash BACKWARD walks K/V as a grid
dimension with O(block) VMEM residency (the forward stays resident while a
row's blocks fit VMEM, which 8192 x 64 do; past that it streams too), so
8k-32k sequences fit a v5e chip (docs/05_performance.md).  ``loss_chunk`` keeps the [B, S, vocab] logits
from ever materializing — at seq 8192 x vocab 50304 they would be ~0.8 GB
bf16 per batch row.  For longer-still contexts shard the token axis
instead (``attn_impl="ring"`` + a ``seq`` mesh axis — docs/04).

The shape (batch 16 x 8192 as 8 accumulation passes of 2 rows, UNROLLED
layers) was tuned in earlier rounds on a machine that no longer exists; its
throughput is not measured on the current machine (PERF.md).  What is known
here: this shape's kernels (the resident forward, the streamed dq / dkv pair)
and the streamed GQA kernels at seq 8192 compile for a v5e
(tests/test_chip_compile.py).  Longer contexts use the same recipe at one
row per pass.
"""

from ml_collections import ConfigDict

from configs.common import model_overrides


def get_config():
    c = ConfigDict()
    c.simulate_cpu_devices = 0
    c.model = "gpt2_125m"
    c.model_overrides = model_overrides(
        seq_len=8192,
        attn_impl="flash",  # the backward streams at this length (flash_plan)
        remat_policy="proj_attn",
        loss_chunk=1024,
        # unrolled layers; per-pass 2 rows keeps the 8k unrolled program
        # small enough to compile quickly
        scan_layers=False,
    )
    c.mesh = ConfigDict(dict(data=-1, model=1, pipe=1, seq=1))
    c.global_batch_size = 16
    c.num_minibatches = 8
    c.steps = 50
    c.optimizer = "adamw"
    c.lr_schedule = "cosine"
    c.ema_decay = 0.0
    c.learning_rate = 3e-4
    c.warmup_steps = 10
    c.weight_decay = 0.1
    c.grad_clip = 1.0
    c.seed = 0
    c.log_every = 10
    c.donate = True
    c.checkpoint_dir = ""
    c.checkpoint_every = 100
    c.data_path = ""
    c.data_format = "flat"
    c.eos_id = 50256
    c.eval_steps = 0
    c.eval_every = 0
    c.keep_best = False
    return c
