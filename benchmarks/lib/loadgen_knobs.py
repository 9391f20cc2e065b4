"""``lib/loadgen.py`` for requests that carry knobs of their own.

    python benchmarks/lib/loadgen_knobs.py <plan.json> <result.json>

The same child process, the same closed loop, the same stamps: this file
starts ``loadgen.main`` and adds one thing.  ``loadgen.one_request`` posts
``prompt``, ``max_new_tokens`` and ``dedupe_token``; a request of the plan
that holds further keys (``denoising_steps``, ``confidence_threshold``: a
block-diffusion model's per-request knobs) gets them put into the body of
its ``POST /v1/submit`` here, at the connection, found again by the dedupe
token that ``loadgen`` builds from the plan's tag and the request's index.
"""

import http.client
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import loadgen  # noqa: E402

KNOBS = {}  # dedupe token -> the request's further fields
PLAIN = ("prompt", "max_new_tokens")


class KnobConnection(http.client.HTTPConnection):
    def request(self, method, url, body=None, headers=None, **kwargs):
        if method == "POST" and url == "/v1/submit":
            fields = json.loads(body)
            fields.update(KNOBS.get(fields.get("dedupe_token"), {}))
            body = json.dumps(fields)
        return super().request(method, url, body, headers or {}, **kwargs)


def main(argv):
    with open(argv[1]) as f:
        plan = json.load(f)
    for idx, req in enumerate(plan["requests"]):
        extra = {k: v for k, v in req.items() if k not in PLAIN}
        if extra:
            KNOBS[f"{plan['tag']}-{idx}"] = extra
    http.client.HTTPConnection = KnobConnection  # loadgen opens it by name
    return loadgen.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv))
