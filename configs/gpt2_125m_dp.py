"""GPT-2 125M, pure data parallelism (BASELINE config 2: v5e-8).

An EIGHT-chip config: ``global_batch_size = 64`` in one pass is 8 rows a
chip on a v5e-8.  On ONE 16 GB v5e a 64-row pass does not fit (the compiler
refuses it at 24.9 GB, and 32 rows at 16.6 GB); run it there with
``--config.num_minibatches=4`` (16 rows a pass, 12.2 GB — what
``chip_smoke.py`` does).
"""

from ml_collections import ConfigDict

from configs.common import model_overrides


def get_config():
    c = ConfigDict()
    c.simulate_cpu_devices = 0
    c.model = "gpt2_125m"
    # flash kernels with tiles derived from the shape, attention residuals
    # saved by the proj_attn remat policy, layers unrolled: the recipe of the
    # benchmark's train cell (PERF.md section 4; its numbers in section 5)
    c.model_overrides = model_overrides(
        attn_impl="flash", remat_policy="proj_attn", scan_layers=False
    )
    c.mesh = ConfigDict(dict(data=-1, model=1, pipe=1, seq=1))
    c.global_batch_size = 64
    c.num_minibatches = 1
    c.steps = 100
    c.optimizer = "adamw"  # adamw | lion | sgd
    c.lr_schedule = "cosine"  # cosine | linear | constant
    c.ema_decay = 0.0  # >0 keeps an EMA shadow of params (eval prefers it)
    c.learning_rate = 6e-4
    c.warmup_steps = 20
    c.weight_decay = 0.1
    c.grad_clip = 1.0
    c.seed = 0
    c.log_every = 10
    c.donate = True
    # optional run plumbing (empty = disabled)
    c.checkpoint_dir = ""
    c.checkpoint_every = 100
    c.data_path = ""
    c.data_format = "flat"  # flat | packed (EOS-delimited docs + segment_ids)
    c.eos_id = 50256
    c.eval_steps = 0
    c.eval_every = 0  # >0: periodic eval during fit (uses the held-out split)
    c.keep_best = False  # snapshot lowest-eval-loss state to {checkpoint_dir}/best
    return c