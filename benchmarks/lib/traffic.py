"""One general traffic generator, driven by a traffic file's parameters.

Everything a run sends comes from ``--seed``: which length each request
has, the order the clients draw them in, and every token.  The lengths are
the stratified quantiles of the stated distributions, so every seed sends
the same SET of sizes in its own order (and prompts and answers are paired
anew): two seeds do the same amount of work, differently laid out, and a
run's spread is the machine's and the layout's, not the luck of a draw of
some tens of lengths.
"""

import math
import random
import statistics


_NORMAL = statistics.NormalDist()


def stratified(dist: dict, n: int) -> list:
    """``n`` whole-number lengths at the mid-quantiles of ``dist``:
    ``{"kind": "lognormal", "median", "sigma", "min", "max"}`` or
    ``{"kind": "uniform", "min", "max"}``."""
    out = []
    for i in range(n):
        p = (i + 0.5) / n
        if dist["kind"] == "lognormal":
            x = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(p))
        elif dist["kind"] == "uniform":
            x = dist["min"] + p * (dist["max"] - dist["min"])
        else:
            raise ValueError(f"unknown length distribution {dist['kind']!r}")
        out.append(int(min(max(round(x), dist["min"]), dist["max"])))
    return out


def make_requests(traffic: dict, seed: int, vocab: int, seq_len: int) -> list:
    """The pool of requests a closed loop's clients draw in order, for one
    run: ``[{"prompt", "max_new_tokens"}]``, ``clients * pool_per_client``
    of them."""
    arrivals = traffic["arrivals"]
    if arrivals["kind"] != "closed":
        raise ValueError(f"unknown arrivals {arrivals['kind']!r}")
    rng = random.Random(seed)
    n = arrivals["clients"] * arrivals["pool_per_client"]
    prompts = stratified(traffic["prompt_tokens"], n)
    outputs = stratified(traffic["output_tokens"], n)
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    requests = []
    for i in range(n):
        p = min(prompts[i], seq_len - 1)
        o = max(1, min(outputs[i], seq_len - p))
        requests.append({
            "prompt": [rng.randrange(1, vocab) for _ in range(p)],
            "max_new_tokens": o,
        })
    return requests


def percentile(values, q: float) -> float:
    """Linear-interpolation percentile (numpy's default), ``q`` in [0, 100]."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def median(values) -> float:
    return percentile(values, 50.0)
