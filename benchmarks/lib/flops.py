"""Operations and bytes an algorithm needs, computed from shapes.

``train_flops_per_token`` is copied from
``tpu_parallel/utils/profiling.py::transformer_flops_per_token`` (dense
models only; ``tests/benchmarks`` checks the copy still agrees).  Recomputed
operations (remat) are never counted: a utilization built on these numbers
cannot pass 100%.
"""


def train_flops_per_token(model: dict) -> float:
    """PaLM-appendix accounting: 6 FLOPs a matmul parameter a token (forward
    2, backward 4) plus ``12 * L * d * T`` for causal attention over T
    positions.  Embedding lookups are gathers and are left out; the untied
    lm_head matmul is in."""
    d, layers = model["d_model"], model["n_layers"]
    matmul_params = model["vocab_size"] * d + layers * (
        4 * d * d + 2 * model.get("mlp_ratio", 4) * d * d
    )
    return 6 * matmul_params + 12 * layers * d * model["seq_len"]


def causal_attention_train_cost(rows: int, model: dict) -> dict:
    """FLOPs and HBM bytes one layer's attention needs for ``rows`` sequences,
    forward and backward, as flash attention computes it.

    Forward: QK^T and PV, two matmuls of ``2 * S * S * d`` each, halved by
    the causal mask.  Backward: five matmuls (S again, dV, dP, dQ, dK) - the
    count of the algorithm, whatever a kernel recomputes on top.  Bytes: q,
    k, v, o read or written once forward; q, k, v, o, do read and dq, dk, dv
    written backward: twelve ``[rows, S, d]`` bf16 tensors.
    """
    s, d = model["seq_len"], model["d_model"]
    one_matmul = 2 * rows * s * s * d / 2
    return {
        "flops": 7 * one_matmul,
        "bytes": 12 * rows * s * d * 2,
    }


def roofline_seconds(cost: dict, peak: dict) -> tuple:
    """The least time the chip could take, and which peak bounds it."""
    t_flops = cost["flops"] / peak["flops"]
    t_bytes = cost["bytes"] / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
