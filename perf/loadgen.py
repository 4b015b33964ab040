"""Load generator: a child process of a serving driver.

It holds no chip. The parent holds the chip and the ServingServer; this
process sends the traffic over loopback TCP through the program's own
client (``ServingClient.generate``), so that client threads never share the
scheduler thread's interpreter lock, and records when it was due to send
each request, when it did, and when every token arrived. Importing the
program's client imports the ``jax`` module (``distkeras_tpu/__init__``
does); the parent starts this process with ``JAX_PLATFORMS=cpu`` and no
code here touches a device.

    python perf/loadgen.py        (the path of its spec arrives on stdin)

The spec names the server's address, the traffic mix's parameters, the
seed, ``mode`` (``open``: requests sent on a schedule whether or not
earlier ones have finished; ``closed``: ``callers`` callers, each sending
its next request when the last completes), the lengths of ramp, window and
drain, and where to write the records. Protocol with the parent: this
process prints ``READY`` when its connections are open, reads one line
``GO <t>`` (a ``time.perf_counter()`` reading: CLOCK_MONOTONIC, shared by
both processes) and treats ``t`` as time zero of the schedule. Load runs
over ``[0, ramp_s + seconds)``; the parent's window is the last
``seconds`` of it.
"""

from __future__ import annotations

import itertools
import json
import os
import queue
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

import numpy as np  # noqa: E402

import traffic  # noqa: E402


class Sender:
    """One connection and the records of what was sent over it."""

    def __init__(self, address: str, t_zero_box: list, timeout_s: float):
        from distkeras_tpu.serving.server import ServingClient

        self._new = lambda: ServingClient(address, timeout=timeout_s,
                                          retry=None)
        self.client = self._new()
        self.t_zero_box = t_zero_box

    def send(self, rec: dict, prompt, max_new: int) -> None:
        """Blocking generate; fills ``rec`` with times relative to zero."""
        zero = self.t_zero_box[0]
        times = rec["token_times"]
        tokens = rec["tokens"]

        def on_token(tok: int) -> None:
            times.append(time.perf_counter() - zero)
            tokens.append(tok)

        rec["sent"] = time.perf_counter() - zero
        try:
            res = self.client.generate(prompt, max_new_tokens=max_new,
                                       on_token=on_token)
            rec["done"] = time.perf_counter() - zero
            rec["reason"] = res.reason
        except Exception as e:  # recorded per request; the run goes on
            rec["error"] = f"{type(e).__name__}: {e}"[:200]
            try:
                self.client.close()
                self.client = self._new()
            except OSError as e2:
                rec["error"] += f"; reconnect failed: {e2}"[:100]


def new_record(uid, due: float, req: dict) -> dict:
    return {"uid": uid, "due": due, "sent": None, "done": None,
            "error": None, "reason": None,
            "prompt_len": int(len(req["prompt"])),
            "max_new": int(req["max_new"]), "token_times": [], "tokens": [],
            "_prompt": req["prompt"]}


def run_open(spec: dict, mix: traffic.Mix, zero_box: list, records: list,
             horizon: float) -> None:
    rng = np.random.default_rng([spec["seed"], 3])
    due = traffic.arrival_times(rng, spec["params"]["arrivals"], horizon)
    reqs = list(itertools.islice(mix.stream(0), len(due)))
    work: "queue.Queue" = queue.Queue()
    senders = [Sender(spec["address"], zero_box, spec["timeout_s"])
               for _ in range(spec["connections"])]

    def worker(sender: Sender) -> None:
        while True:
            rec, req = work.get()
            sender.send(rec, req["prompt"], req["max_new"])

    threads = [threading.Thread(target=worker, args=(s,), daemon=True)
               for s in senders]
    for th in threads:
        th.start()
    print("READY", flush=True)
    zero_box[0] = float(sys.stdin.readline().split()[1])
    for i, (t_due, req) in enumerate(zip(due, reqs)):
        wait = zero_box[0] + t_due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        rec = new_record(str(i), float(t_due), req)
        records.append(rec)
        work.put((rec, req))


def run_closed(spec: dict, mix: traffic.Mix, zero_box: list, records: list,
               horizon: float) -> None:
    callers = int(spec["params"]["callers"])
    lock = threading.Lock()
    go = threading.Event()
    ready = threading.Semaphore(0)
    senders = [Sender(spec["address"], zero_box, spec["timeout_s"])
               for _ in range(callers)]

    def caller(cid: int, sender: Sender) -> None:
        reqs = mix.stream(cid)
        req = next(reqs)        # draws the first block before READY
        ready.release()
        go.wait()
        zero = zero_box[0]
        for n in itertools.count():
            now = time.perf_counter() - zero
            if now >= horizon:
                return
            rec = new_record(f"{cid}.{n}", now, req)
            with lock:
                records.append(rec)
            sender.send(rec, req["prompt"], req["max_new"])
            req = next(reqs)

    threads = [threading.Thread(target=caller, args=(i, s), daemon=True)
               for i, s in enumerate(senders)]
    for th in threads:
        th.start()
    for _ in threads:
        ready.acquire()
    print("READY", flush=True)
    zero_box[0] = float(sys.stdin.readline().split()[1])
    wait = zero_box[0] - time.perf_counter()
    if wait > 0:
        time.sleep(wait)
    go.set()
    time.sleep(max(0.0, zero_box[0] + horizon - time.perf_counter()))


def main(argv) -> int:
    # started early so that its imports overlap the parent's set-up; the
    # spec arrives once the server is up
    from distkeras_tpu.serving.server import ServingClient  # noqa: F401

    with open(sys.stdin.readline().strip()) as f:
        spec = json.load(f)
    mix = traffic.Mix(spec["params"], spec["seed"])
    horizon = float(spec["ramp_s"]) + float(spec["seconds"])
    zero_box = [0.0]
    records: list = []
    (run_open if spec["mode"] == "open" else run_closed)(
        spec, mix, zero_box, records, horizon)
    # drain: wait for what was sent, up to the limit
    t_stop = zero_box[0] + horizon + float(spec["drain_s"])
    while time.perf_counter() < t_stop:
        if all(r["done"] is not None or r["error"] is not None
               for r in list(records)):
            break
        time.sleep(0.02)
    drained = time.perf_counter() - zero_box[0]
    out = []
    for r in list(records):
        r = dict(r)
        if r["done"] is None and r["error"] is None and \
                spec["mode"] == "open":
            r["error"] = "unfinished at the drain limit"
        out.append(r)       # closed loop: cut by the close, not a failure
    # a seeded sample of finished requests keeps its prompt and tokens for
    # the parent's check against the reference; the rest drop them
    t0 = float(spec["ramp_s"])
    ok = [i for i, r in enumerate(out)
          if r["error"] is None and r["done"] is not None
          and r["due"] >= t0 and r["tokens"]]
    rng = np.random.default_rng([spec["seed"], 5])
    keep = set(rng.permutation(ok)[:int(spec["check_sample"])].tolist())
    for i, r in enumerate(out):
        prompt = r.pop("_prompt")
        if i in keep:
            r["prompt"] = [int(t) for t in prompt]
        else:
            r.pop("tokens")
    lateness = [r["sent"] - r["due"] for r in out if r["sent"] is not None]
    with open(spec["out"], "w") as f:
        json.dump({"records": out, "lateness": lateness,
                   "drained_s": drained, "horizon_s": horizon}, f)
    print("DONE", flush=True)
    sys.stdout.flush()
    os._exit(0)  # daemon threads may sit in a socket read; the records are out


if __name__ == "__main__":
    main(sys.argv[1:])
