"""Operations and bytes the grouped matmuls of LATENT two-matrix experts
need, from the program's own counters (``lib/moe_cost.py`` counts three
matrices of ``d_model x width`` an expert: read on these experts it would
overstate the least time by half).

One held assignment (a token's row at one of the experts held here) costs two
matmuls against ``[latent, width]`` weights, ``W2 relu(W1 v)^2``: ``4 * latent
* width`` FLOPs.  A call (one layer's pass over one program step's rows) has
to read the TWO weight matrices of every expert it TOUCHES once, its rows in
at the latent width, and write them out at the latent width; the
``width``-wide intermediate between the two matmuls is left out (a fused
kernel would keep it on the chip), and so are the two latent projections
around the routed sum (they are not grouped matmuls and have their own
metric).  Both are linear in what the counters count, so totals over any set
of calls give that set's least time: a sum of lower bounds, which cannot pass
the measured time.
"""


def routed_experts_cost(held_rows: float, touched: float, experts: dict) -> dict:
    """FLOPs and HBM bytes of a set of calls that routed ``held_rows`` rows
    to held experts and touched ``touched`` experts (each summed over the
    calls); ``experts`` holds ``latent``, ``width``, ``bytes_per_value``."""
    l, w, b = experts["latent"], experts["width"], experts["bytes_per_value"]
    return {
        "flops": held_rows * 4 * l * w,
        "bytes": b * (touched * 2 * l * w + held_rows * 2 * l),
    }
