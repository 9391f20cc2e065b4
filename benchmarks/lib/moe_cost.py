"""Operations and bytes the routed experts' grouped matmuls need, from the
program's own counters.

One held assignment (a token's row at one of the experts held here) costs
three matmuls against ``[d_model, width]`` weights: ``6 * d_model * width``
FLOPs.  A call (one layer's pass over one program step's rows) has to read
the three weight matrices of every expert it TOUCHES once, its rows in, and
write them out; the ``width``-wide intermediates between the three matmuls
are left out (a fused kernel would keep them on the chip).  Both are linear
in what the counters count, so totals over any set of calls give that set's
least time: a sum of lower bounds, which cannot pass the measured time.
"""


def routed_experts_cost(held_rows: float, touched: float, experts: dict) -> dict:
    """FLOPs and HBM bytes of a set of calls that routed ``held_rows`` rows
    to held experts and touched ``touched`` experts (each summed over the
    calls); ``experts`` holds ``d_model``, ``width``, ``bytes_per_value``."""
    d, w, b = experts["d_model"], experts["width"], experts["bytes_per_value"]
    return {
        "flops": held_rows * 6 * d * w,
        "bytes": b * (touched * 3 * d * w + held_rows * 2 * d),
    }


def counters_between(before: dict, after: dict) -> dict:
    """What the expert counters of ``ServingMetrics.summary()`` gained
    between two readings: ``{"calls", "held_rows", "touched"}``."""
    def totals(c):
        calls = c.get("moe_calls", 0)
        return (calls, c.get("moe_assignments_held", 0),
                c.get("moe_experts_touched_mean", 0.0) * calls)

    a, b = totals(before), totals(after)
    return {"calls": b[0] - a[0], "held_rows": b[1] - a[1],
            "touched": b[2] - a[2]}
