"""The load generator: a child process that never imports JAX and speaks
HTTP/SSE to the daemon on loopback.

    python benchmarks/lib/loadgen.py <plan.json> <result.json>

Plan: ``{"port", "t0": <time.monotonic() at which offset 0 falls>,
"requests": [{"prompt", "max_new_tokens"}], "clients", "drain_timeout_s",
"io_timeout_s", "tag"}``.  ``time.monotonic()`` is the machine's
CLOCK_MONOTONIC, so parent and child read the same clock.

A closed loop: ``clients`` threads start at ``t0``; client ``k`` sends
requests ``k``, ``k + clients``, ... of the pool, each when the last
completed, until the pool is used up or the parent writes the line ``stop``
to this process's standard input; what is in flight then is cancelled.  Once
every client's first stream is attached (the daemon has answered its
``GET``), the line ``attached <offset>`` goes to standard output: from then
on no stream can deliver, in one burst, tokens that were made while it
waited to attach.

Every request: one ``POST /v1/submit`` (its round trip timed), then one
``GET /v1/stream/<id>`` read to the terminal event, each token stamped as it
arrives.  Times in the result are offsets from ``t0``.
"""

import http.client
import json
import sys
import threading
import time


def one_request(port, req, idx, tag, t0, stop, out, io_timeout=120):
    rec = {"idx": idx, "ok": False, "tokens": [], "token_s": [],
           "error": None, "cancelled": False}
    out[idx] = rec
    conn = None
    try:
        rec["sent_s"] = time.monotonic() - t0
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=io_timeout)
        body = json.dumps({
            "prompt": req["prompt"], "max_new_tokens": req["max_new_tokens"],
            "dedupe_token": f"{tag}-{idx}",
        })
        conn.request("POST", "/v1/submit", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        record = json.loads(resp.read() or b"{}")
        rec["submit_s"] = time.monotonic() - t0 - rec["sent_s"]
        if resp.status != 200:
            rec["error"] = f"submit {resp.status}: {record.get('finish_reason')}"
            return
        rid = record["request_id"]
        conn.close()
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=io_timeout)
        conn.request("GET", f"/v1/stream/{rid}")
        resp = conn.getresponse()
        rec["attached_s"] = time.monotonic() - t0
        if resp.status != 200:
            rec["error"] = f"stream {resp.status}"
            return
        while True:
            if stop.is_set():
                rec["cancelled"] = True
                cancel = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
                cancel.request("POST", f"/v1/cancel/{rid}", b"{}",
                               {"Content-Type": "application/json"})
                cancel.getresponse().read()
                cancel.close()
                return
            line = resp.readline()
            if not line:
                rec["error"] = "stream ended without a terminal event"
                return
            if not line.startswith(b"data: "):
                continue
            ev = json.loads(line[6:])
            if "token" in ev:
                rec["tokens"].append(ev["token"])
                rec["token_s"].append(time.monotonic() - t0)
            if ev.get("finished"):
                rec["finish_reason"] = ev.get("finish_reason")
                rec["ok"] = ev.get("status") == "finished"
                if not rec["ok"]:
                    rec["error"] = f"finished as {ev.get('status')}"
                return
    except Exception as exc:  # a boundary: the failure is the result
        rec["error"] = f"{type(exc).__name__}: {exc}"
    finally:
        if conn is not None:
            conn.close()


def run_closed(plan):
    t0, out, stop = plan["t0"], {}, threading.Event()
    clients, reqs = plan["clients"], plan["requests"]

    def client(k):
        for idx in range(k, len(reqs), clients):
            if stop.is_set():
                return
            one_request(plan["port"], reqs[idx], idx, plan["tag"], t0, stop,
                        out, plan.get("io_timeout_s", 120))

    def wait_for_stop():
        for line in sys.stdin:
            if line.strip() == "stop":
                stop.set()
                return

    delay = t0 - time.monotonic()
    if delay > 0:
        time.sleep(delay)
    threading.Thread(target=wait_for_stop, daemon=True).start()
    threads = [
        threading.Thread(target=client, args=(k,), daemon=True)
        for k in range(clients)
    ]
    for th in threads:
        th.start()
    first = range(min(clients, len(reqs)))
    while any(th.is_alive() for th in threads) and not all(
        "attached_s" in out.get(k, {}) or out.get(k, {}).get("error")
        for k in first
    ):
        time.sleep(0.01)
    print(f"attached {time.monotonic() - t0:.4f}", flush=True)
    while any(th.is_alive() for th in threads) and not stop.is_set():
        time.sleep(0.01)
    deadline = time.monotonic() + plan["drain_timeout_s"]
    for th in threads:
        th.join(max(0.0, deadline - time.monotonic()))
    for idx, rec in list(out.items()):
        if not rec["ok"] and not rec["cancelled"] and rec["error"] is None:
            rec["error"] = "unfinished at the drain deadline"
    return [out[i] for i in sorted(out)]


def main(argv):
    with open(argv[1]) as f:
        plan = json.load(f)
    records = run_closed(plan)
    with open(argv[2], "w") as f:
        json.dump({"records": records}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
