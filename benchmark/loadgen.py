#!/usr/bin/env python3
"""The load generator: a child process of the runner that never imports
JAX (standard library only), so it cannot touch the chip and shares
nothing with the server but the machine's cores.

It reads one JSON job from standard input (the gateway's address, the
monotonic clock reading at which the window starts, and the schedule
``traffic.serving_schedule`` made), sends every request over HTTP with
server-sent events the way ``serving/client.py`` does, stamps each
streamed token with its own clock, and writes one JSON object of
records to standard output. ``CLOCK_MONOTONIC`` is shared by all
processes of a machine, so the runner reads the same clock.

Open loop: every request is sent at its due time whether or not earlier
ones have ended, and is timed from when it was due. Closed loop:
``clients`` callers each send their next request when their last ended.
Requests that have not ended ``drain_limit_s`` after the window count as
failed.
"""

from __future__ import annotations

import http.client
import json
import socket
import sys
import threading
import time


def stream_one(host: str, port: int, prompt, max_new: int, deadline: float,
               record: dict, stall_s: float = 30.0) -> None:
    """POST one streaming generate and fill ``record``. A server that
    sends nothing (not even a keep-alive) for ``stall_s`` has failed
    the request."""
    conn = http.client.HTTPConnection(host, port, timeout=stall_s)
    token_times, tokens = record["token_times"], record["tokens"]
    try:
        conn.request(
            "POST", "/v1/generate?stream=1",
            body=json.dumps({"prompt": prompt,
                             "max_new_tokens": max_new}).encode(),
            headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            record["error"] = f"http {resp.status}: {resp.read()[:200]!r}"
            return
        data = []
        while True:
            left = deadline - time.monotonic()
            if left <= 0:
                record["error"] = "drain limit passed"
                return
            conn.sock.settimeout(min(left, stall_s))
            line = resp.readline()
            if not line:
                record["error"] = "stream ended without a terminal event"
                return
            line = line.rstrip(b"\r\n")
            if line.startswith(b"data:"):
                data.append(line[5:].strip())
                continue
            if line or not data:
                continue            # comment ping, id line or stray blank
            event = json.loads(b"".join(data))
            data = []
            if event.get("done"):
                record["finish_reason"] = event.get("finish_reason")
                record["timing"] = event.get("timing")
                final = event.get("tokens")
                if final is not None and list(final) != tokens:
                    record["error"] = "streamed tokens differ from the " \
                                      "terminal list"
                    return
                record["ok"] = (event.get("finish_reason") == "length"
                                and len(tokens) == max_new)
                if not record["ok"]:
                    record["error"] = (
                        f"ended {event.get('finish_reason')!r} with "
                        f"{len(tokens)} of {max_new} tokens")
                return
            got = event.get("tokens")
            if got:
                now = time.monotonic()
                tokens.extend(int(t) for t in got)
                token_times.extend([now] * len(got))
    except (OSError, socket.timeout, http.client.HTTPException,
            ValueError) as e:
        record["error"] = f"{type(e).__name__}: {e}"
    finally:
        record["ended"] = time.monotonic()
        conn.close()


class Heartbeat:
    """Sleeps 10 ms at a time and notes the worst oversleep: how long
    this process was kept off the processor (a stalled machine shows in
    the server's heartbeat at the same moment)."""

    def __init__(self):
        self.worst_s, self.worst_at = 0.0, None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        while not self._stop.is_set():
            t = time.monotonic()
            time.sleep(0.01)
            over = time.monotonic() - t - 0.01
            if over > self.worst_s:
                self.worst_s, self.worst_at = over, t

    def start(self):
        self._thread.start()
        return self

    def stop(self) -> dict:
        self._stop.set()
        self._thread.join(timeout=1.0)
        return {"stall_max_ms": 1000.0 * self.worst_s,
                "stall_at": self.worst_at}


def new_record(req: dict, due_abs, in_window: bool) -> dict:
    return {"id": req["id"], "due": due_abs, "sent": None, "ended": None,
            "in_window": in_window, "ok": False, "error": None,
            "prompt_len": len(req["prompt"]), "max_new": req["max_new"],
            "finish_reason": None, "timing": None, "token_times": [],
            "tokens": []}


def run_open_loop(job: dict, host: str, port: int) -> list:
    t0 = job["t0"]
    deadline = t0 + job["window_s"] + job["drain_limit_s"]
    records, threads = [], []

    def fire(req, rec):
        rec["sent"] = time.monotonic()
        stream_one(host, port, req["prompt"], req["max_new"], deadline,
                   rec)

    window_end = t0 + job["window_s"]
    for req in job["requests"]:
        due = t0 + req["due"]
        while True:     # sleep to the due time; past the window, stop
            now = time.monotonic()   # once its requests have all ended
            if now >= due:
                break
            if now > window_end and all(
                    r["ended"] is not None for r in records
                    if r["in_window"]):
                return records
            time.sleep(min(due - now, 0.25 if now > window_end
                           else due - now))
        rec = new_record(req, due, req["in_window"])
        records.append(rec)
        th = threading.Thread(target=fire, args=(req, rec), daemon=True)
        th.start()
        threads.append(th)
    for rec, th in zip(records, threads):
        if rec["in_window"] or rec["due"] < window_end:
            th.join(timeout=max(deadline - time.monotonic(), 0.0) + 5.0)
    return records


def run_closed_loop(job: dict, host: str, port: int) -> list:
    """``clients`` callers take requests in order from the lead-in's
    start until the window's end; a request counts for the window when
    it was sent inside it."""
    t0 = job["t0"]
    start, end = t0 - job["lead_in_s"], t0 + job["window_s"]
    deadline = end + job["drain_limit_s"]
    lock = threading.Lock()
    queue = iter(job["requests"])
    records = []

    def client():
        while time.monotonic() < end:
            with lock:
                req = next(queue, None)
            if req is None:
                return
            now = time.monotonic()
            rec = new_record(req, now, t0 <= now < end)
            rec["sent"] = now
            with lock:
                records.append(rec)
            stream_one(host, port, req["prompt"], req["max_new"],
                       deadline, rec)

    wait = start - time.monotonic()
    if wait > 0:
        time.sleep(wait)
    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(job["clients"])]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=max(deadline - time.monotonic(), 0.0) + 5.0)
    return records


def main() -> int:
    job = json.load(sys.stdin)
    host, port = job["address"].split("://", 1)[-1].rsplit(":", 1)
    threading.stack_size(256 * 1024)
    run = run_open_loop if job["kind"] == "open_loop" else run_closed_loop
    beat = Heartbeat().start()
    records = run(job, host, int(port))
    stall = beat.stop()
    window_end = job["t0"] + job["window_s"]
    for rec in records:   # a thread that outlived its join: failed
        if rec["ended"] is None:   # (lead-out requests are just dropped)
            rec["ok"] = False
            if rec["in_window"] or rec["due"] < window_end:
                rec["error"] = "still running at the end"
    json.dump({"records": records, "ended": time.monotonic(),
               "heartbeat": stall}, sys.stdout)
    sys.stdout.write("\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
