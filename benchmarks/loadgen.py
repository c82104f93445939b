#!/usr/bin/env python3
"""The load generator: a child process that never imports JAX.

    python3 benchmarks/loadgen.py <plan.json>

The plan (written by run.py) names the server's URL, the resolved traffic
mix, the seed and the phases.  The child plays a warm-up phase and then the
measured window against ``/v1/completions`` (token-id prompts, streamed,
greedy, ``ignore_eos``), stamps every SSE frame with its own monotonic
clock, and writes one JSON record per request to the plan's ``records``
file.  It does no metric arithmetic: run.py reduces the records with
``clientmetrics.py``.

Open loop: each request has a due time and is timed from it, however late
it was sent (``sent - due`` is the generator's own lateness).  Closed loop:
``clients`` tasks each send their next request when the last completed.

Events for the parent, one JSON object per line on stdout:
  {"event": "window_start", "epoch": ...}   first measured due time
  {"event": "window_end", "epoch": ...}     the window's last second passed
  {"event": "done", ...}                    all measured requests finished
"""

from __future__ import annotations

import asyncio
import json
import sys
import time

import aiohttp

import traffic


def emit(**kw) -> None:
    sys.stdout.write(json.dumps(kw) + "\n")
    sys.stdout.flush()


class Player:
    def __init__(self, plan: dict) -> None:
        self.plan = plan
        self.mix = plan["mix"]
        self.url = plan["url"] + "/v1/completions"
        self.model = plan["model"]
        self.vocab = plan["vocab"]
        self.seed = plan["seed"]
        self.prefix = traffic.shared_prefix(self.mix, self.seed, self.vocab)
        self.records = []
        self.tasks = set()
        self.t0 = time.perf_counter()       # the child's clock origin
        self.epoch0 = time.time()
        self.stop_sending = False

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def body(self, phase: str, i: int, req: dict) -> bytes:
        return json.dumps({
            "model": self.model,
            "prompt": traffic.prompt_ids(self.prefix, req, self.seed, phase,
                                         i, self.vocab),
            "max_tokens": req["max_tokens"], "stream": True,
            "temperature": 0.0, "ignore_eos": True}).encode()

    async def one(self, session, phase: str, i: int, req: dict,
                  body: bytes, due: float) -> dict:
        rec = {"phase": phase, "i": i, "due": due, "sent": None,
               "first": None, "frames": [], "done": False,
               "cancelled": False, "status": None, "error": None,
               "max_tokens": req["max_tokens"],
               "prompt_tokens": len(self.prefix) + req["own_tokens"]
               + req.get("session_tokens", 0),
               "n_tokens": 0, "end": None}
        self.records.append(rec)
        try:
            rec["sent"] = self.now()
            async with session.post(
                    self.url, data=body,
                    headers={"Content-Type": "application/json"}) as resp:
                rec["status"] = resp.status
                if resp.status != 200:
                    rec["error"] = (await resp.text())[:200]
                    return rec
                async for line in resp.content:
                    if not line.startswith(b"data: "):
                        continue
                    t = self.now()
                    payload = line[6:].strip()
                    if payload == b"[DONE]":
                        rec["done"] = True
                        break
                    n = len(json.loads(payload)["llmd"]["tok"])
                    if n:
                        if rec["first"] is None:
                            rec["first"] = t
                        rec["frames"].append([t, n])
                        rec["n_tokens"] += n
        except asyncio.CancelledError:
            rec["cancelled"] = True
        except Exception as e:          # recorded: counts as a failure
            rec["error"] = f"{type(e).__name__}: {e}"[:200]
        rec["end"] = self.now()
        return rec

    def spawn(self, coro) -> asyncio.Task:
        task = asyncio.ensure_future(coro)
        self.tasks.add(task)
        task.add_done_callback(self.tasks.discard)
        return task

    # ---------------- open loop ----------------

    async def play_open(self, session, phase: str, seconds: float,
                        start: float) -> list:
        """Send the phase's requests at their due times (``start`` + due on
        the child's clock).  Returns the tasks."""
        sched = traffic.build_schedule(self.mix, self.seed, seconds, phase,
                                       rate=self.plan.get("rate_rps"))
        reqs = sched["requests"]
        bodies = [self.body(phase, i, r) for i, r in enumerate(reqs)]
        tasks = []
        for i, r in enumerate(reqs):
            due = start + r["due"]
            delay = due - self.now()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(self.spawn(
                self.one(session, phase, i, r, bodies[i], due)))
        return tasks

    async def run_open(self, session) -> None:
        warm, secs = self.plan["warmup_seconds"], self.plan["seconds"]
        # Bodies are built before the clock starts to matter.
        start = self.now() + 0.2
        if warm > 0:
            await self.play_open(session, "warmup", warm, start)
        w0 = start + warm
        emit(event="window_start", epoch=self.epoch0 + w0, t=w0)
        tasks = await self.play_open(session, "window", secs, w0)
        await asyncio.sleep(max(0.0, w0 + secs - self.now()))
        emit(event="window_end", epoch=self.epoch0 + w0 + secs,
             t=w0 + secs)
        if tasks:
            _, pending = await asyncio.wait(
                tasks, timeout=self.plan["drain_seconds"])
            for t in pending:           # counted as failed by the parent
                t.cancel()
        self.window = (w0, w0 + secs)

    # ---------------- closed loop ----------------

    async def client(self, session, phase_of, queue: list, first_frac):
        first = True
        while not self.stop_sending and queue:
            i, r = queue.pop(0)
            if first and first_frac is not None:
                # Ramp: a client's first answer is cut to a random share, as
                # if the run had started in the middle of it, so the clients
                # do not finish in lockstep.
                r = dict(r, max_tokens=max(1, int(r["max_tokens"]
                                                  * first_frac)))
            first = False
            phase = phase_of()
            await self.one(session, phase, i, r, self.body("window", i, r),
                           self.now())

    async def run_closed(self, session) -> None:
        warm, secs = self.plan["warmup_seconds"], self.plan["seconds"]
        sched = traffic.build_schedule(self.mix, self.seed, secs, "window")
        queue = list(enumerate(sched["requests"]))
        start = self.now()
        w0 = start + warm

        def phase_of():
            return "window" if self.now() >= w0 else "warmup"

        fracs = traffic._stream(
            self.mix.get("order_seed", self.seed), "ramp").uniform(
            0.05, 1.0, size=sched["clients"])
        clients = [self.spawn(self.client(session, phase_of, queue,
                                          float(fracs[c])))
                   for c in range(sched["clients"])]
        await asyncio.sleep(max(0.0, w0 - self.now()))
        emit(event="window_start", epoch=self.epoch0 + w0, t=w0)
        await asyncio.sleep(max(0.0, w0 + secs - self.now()))
        self.stop_sending = True
        emit(event="window_end", epoch=self.epoch0 + w0 + secs,
             t=w0 + secs)
        # Requests sent inside the window still owe their first token.
        deadline = self.now() + self.plan["drain_seconds"]
        while self.now() < deadline and any(
                r["phase"] == "window" and r["first"] is None
                and r["end"] is None for r in self.records):
            await asyncio.sleep(0.05)
        for t in list(self.tasks):
            t.cancel()
        await asyncio.gather(*clients, return_exceptions=True)
        self.window = (w0, w0 + secs)

    async def main(self) -> None:
        timeout = aiohttp.ClientTimeout(total=None, sock_read=300)
        conn = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(timeout=timeout,
                                         connector=conn) as session:
            if self.mix["loop"] == "open":
                await self.run_open(session)
            else:
                await self.run_closed(session)
            # Warm-up stragglers of an open loop: let them end, briefly.
            if self.tasks:
                _, pending = await asyncio.wait(list(self.tasks), timeout=5)
                for t in pending:
                    t.cancel()
                await asyncio.gather(*pending, return_exceptions=True)
        with open(self.plan["records"], "w") as f:
            f.write(json.dumps({"window": self.window,
                                "epoch0": self.epoch0,
                                "loop": self.mix["loop"]}) + "\n")
            for rec in self.records:
                f.write(json.dumps(rec) + "\n")
        emit(event="done", requests=len(self.records))


def main() -> int:
    with open(sys.argv[1]) as f:
        plan = json.load(f)
    asyncio.run(Player(plan).main())
    return 0


if __name__ == "__main__":
    sys.exit(main())
