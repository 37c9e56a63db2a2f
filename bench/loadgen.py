"""Open-loop load generator, run as a child process of the benchmark.

    python3 bench/loadgen.py < plan.json > records.jsonl

It imports nothing but the standard library (never JAX), so it holds no
chip and shares no interpreter lock with the server.  The plan (one JSON
object on standard input) gives the server's address, the absolute
``time.monotonic()`` at which the window opens (``t0``; the clock is shared
by every process of the machine), a deadline after which an unfinished
request counts as failed, and the requests with their due offsets.  Each
request is sent on its own thread at ``t0 + due`` whether or not earlier
ones have finished.  One JSON record per request is written to standard
output when all have ended: due, send, first-delta, every delta and end
times (absolute monotonic seconds), HTTP status, whether a stream ended in
``[DONE]``, the text received and the server's ``x_llmbridge`` disclosure.
"""
from __future__ import annotations

import http.client
import json
import sys
import threading
import time


def _stream(resp, rec):
    text = []
    while True:
        line = resp.fp.readline()
        if not line:
            break
        now = time.monotonic()
        line = line.strip()
        if not line.startswith(b"data: "):
            continue
        data = line[len(b"data: "):]
        if data == b"[DONE]":
            rec["done"] = True
            break
        frame = json.loads(data)
        if "error" in frame:
            rec["error"] = str(frame["error"].get("code", "error"))
            break
        choice = frame["choices"][0]
        if "content" in choice.get("delta", {}):
            rec["deltas"].append(now)
            text.append(choice["delta"]["content"])
        if "x_llmbridge" in frame:
            rec["x"] = frame["x_llmbridge"]
    rec["text"] = "".join(text)


def _one(host, port, t0, deadline, req, rec):
    due = t0 + req["due"]
    wait = due - time.monotonic()
    if wait > 0:
        time.sleep(wait)
    rec["sent"] = time.monotonic()
    conn = http.client.HTTPConnection(host, port,
                                      timeout=max(1.0, deadline - rec["sent"]))
    try:
        conn.request("POST", "/v1/chat/completions", json.dumps(req["body"]),
                     {"Content-Type": "application/json",
                      "x-request-id": req["rid"]})
        resp = conn.getresponse()
        rec["status"] = resp.status
        if resp.status != 200:
            rec["error"] = resp.read().decode(errors="replace")[:200]
        elif req["stream"]:
            _stream(resp, rec)
        else:
            body = json.loads(resp.read())
            rec["deltas"].append(time.monotonic())
            rec["text"] = body["choices"][0]["message"]["content"]
            rec["x"] = body.get("x_llmbridge", {})
            rec["done"] = True
    except (OSError, http.client.HTTPException, ValueError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:200]
    finally:
        rec["end"] = time.monotonic()
        conn.close()


def main() -> int:
    plan = json.loads(sys.stdin.read())
    host, port = plan["host"], plan["port"]
    t0, deadline = plan["t0"], plan["deadline"]
    records, threads = [], []
    for req in sorted(plan["requests"], key=lambda r: r["due"]):
        rec = {"i": req["i"], "rid": req["rid"], "stream": req["stream"],
               "due": t0 + req["due"],
               "sent": None, "deltas": [], "end": None, "status": None,
               "done": False, "error": None, "text": "", "x": {}}
        records.append(rec)
        # threads start just ahead of their due time, so thousands of idle
        # sleepers never pile up
        lead = t0 + req["due"] - 0.05 - time.monotonic()
        if lead > 0:
            time.sleep(lead)
        t = threading.Thread(target=_one, args=(host, port, t0, deadline, req,
                                                rec), daemon=True)
        t.start()
        threads.append(t)
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.monotonic()) + 5.0)
    for rec in records:
        sys.stdout.write(json.dumps(rec) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
