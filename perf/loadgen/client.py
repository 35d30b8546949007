"""The load generator's process: every request of a run is sent from here.

A process of its own, so the server under test never shares its interpreter
lock with the client (the discipline of ``tools/grpc_siege.py``, copied), and
one that never imports JAX, so it can never take the chip from the parent.
One thread, one asyncio loop, ``grpc.aio`` channels: few threads make steady
load.  The parent (``perf/run.py``) drives it with one JSON object a line on
stdin and reads one a line from stdout:

``connect``   open the channels to ``localhost:<port>``
``generate``  run Generate RPCs (set-up: reference check, warm-up), reply
              with each stream's tokens and log-probabilities
``window``    build every payload of a plan, say ``ready``, wait for ``go``,
              say ``opened`` when the measured window starts (at once, or
              after a closed loop's ramp), ``closed`` the moment it ends,
              drain, say ``done`` with the samples.  With ``tail_s`` > 0
              (``--trace 2``) the same traffic goes on after ``closed``
              until the parent says ``stop`` (or ``tail_s`` have passed),
              and only then drains: the parent traces that tail
``quit``      close the channels and exit

Times are ``time.monotonic()``, which on Linux is one clock for every
process of the machine, so the parent can place its counter snapshots and
its trace slice on the same axis.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

PERF_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (PERF_DIR, os.path.dirname(PERF_DIR)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import grpc  # noqa: E402
from harness.sizes import prompt_tokens  # noqa: E402

SERVICE = "tpulab.inference.GRPCService"
CHANNEL_OPTIONS = (("grpc.max_receive_message_length", -1),
                   ("grpc.max_send_message_length", -1),
                   # a channel of its own is a connection of its own
                   ("grpc.use_local_subchannel_pool", 1))


def _pb():
    from tpulab.rpc.protos import inference_pb2
    return inference_pb2


class Client:
    def __init__(self):
        self.pb = _pb()
        self.channels = []
        self._rr = 0

    async def connect(self, port: int, channels: int) -> None:
        await self.close()
        self.channels = [
            grpc.aio.insecure_channel(f"localhost:{port}",
                                      options=CHANNEL_OPTIONS)
            for _ in range(max(1, channels))]
        for ch in self.channels:
            await asyncio.wait_for(ch.channel_ready(), timeout=60)

    async def close(self) -> None:
        for ch in self.channels:
            await ch.close()
        self.channels = []

    def _channel(self):
        self._rr = (self._rr + 1) % len(self.channels)
        return self.channels[self._rr]

    # -- payloads -------------------------------------------------------------
    def generate_payload(self, model: str, prompt, steps: int,
                         logprobs: bool = False) -> bytes:
        return self.pb.GenerateRequest(
            model_name=model, prompt=[int(t) for t in prompt],
            steps=int(steps), return_logprobs=logprobs).SerializeToString()

    # -- one call -------------------------------------------------------------
    async def generate_stream(self, payload: bytes, rec: dict,
                              keep_tokens: bool = False,
                              on_first=None) -> None:
        """One Generate stream.  ``rec`` gets ``sent``, ``times`` (arrival
        of every token), ``ok`` and ``error``; with ``keep_tokens`` also the
        tokens and their log-probabilities."""
        pb = self.pb
        call = self._channel().unary_stream(
            f"/{SERVICE}/Generate", request_serializer=None,
            response_deserializer=pb.GenerateResponse.FromString)
        rec["sent"] = time.monotonic()
        rec["times"] = times = []
        rec["ok"] = False
        toks, lps = [], []
        in_range = True
        vocab = rec.get("vocab") or (1 << 31)
        stream = call(payload)
        try:
            async for resp in stream:
                if resp.final:
                    if resp.status.code not in (pb.SUCCESS, 0):
                        rec["error"] = (pb.StatusCode.Name(resp.status.code)
                                        + ": " + resp.status.message)
                        return
                    rec["ok"] = True
                    break
                times.append(time.monotonic())
                if on_first is not None and len(times) == 1:
                    on_first()
                in_range &= 0 <= resp.token < vocab
                if keep_tokens:
                    toks.append(int(resp.token))
                    lps.append(float(resp.logprob))
            else:
                rec["error"] = "stream ended without a final response"
        except asyncio.CancelledError:
            stream.cancel()
            raise
        except grpc.aio.AioRpcError as e:
            rec["error"] = f"{e.code().name}: {e.details()}"
        finally:
            rec["in_range"] = in_range
            if keep_tokens:
                rec["tokens"], rec["logprobs"] = toks, lps

    # -- set-up traffic ---------------------------------------------------------
    async def op_generate(self, cmd: dict) -> dict:
        recs = [{} for _ in cmd["requests"]]
        sem = asyncio.Semaphore(int(cmd.get("concurrency", 1)))

        async def one(req, rec):
            async with sem:
                prompt = req.get("prompt")
                if prompt is None:      # made here: no megabytes on the pipe
                    prompt = prompt_tokens(cmd["seed"], req["index"],
                                           req["prompt_len"], cmd["vocab"])
                payload = self.generate_payload(
                    cmd["model"], prompt, req["steps"],
                    bool(cmd.get("logprobs")))
                await asyncio.wait_for(
                    self.generate_stream(payload, rec, keep_tokens=True),
                    timeout=float(cmd.get("timeout_s", 900)))
        await asyncio.gather(*(one(q, r) for q, r in
                               zip(cmd["requests"], recs)))
        return {"results": [
            {"tokens": r["tokens"], "logprobs": r["logprobs"],
             "ok": r["ok"], "error": r.get("error")} for r in recs]}

    # -- the measured window ------------------------------------------------------
    def build_payloads(self, cmd: dict) -> list:
        return [self.generate_payload(
            cmd["model"],
            prompt_tokens(cmd["seed"], q["index"], q["prompt_len"],
                          cmd["vocab"]), q["steps"])
            for q in cmd["plan"]["requests"]]

    async def run_window(self, cmd: dict, payloads: list, say,
                         stopped=None) -> dict:
        """``stopped(tail_s)`` is awaited after ``closed`` where the command
        has ``tail_s``: it returns when the parent says stop, or after that
        many seconds."""
        plan = cmd["plan"]
        seconds = plan["seconds"]
        tail_s = float(cmd.get("tail_s", 0))
        recs: list = []
        tail_recs: list = []    # an open loop's arrivals after the window
        tasks: set = set()

        async def start(i: int, due: float = None, on_first=None,
                        into: list = recs) -> None:
            q = plan["requests"][i]
            rec = {"index": q["index"], "due": due,
                   "steps": q.get("steps"), "vocab": cmd.get("vocab")}
            into.append(rec)
            # no "end": the call was still in flight when it was cancelled
            await self.generate_stream(payloads[i], rec, on_first=on_first)
            rec["end"] = time.monotonic()

        def opened(t0: float) -> float:
            say({"event": "opened", "t_start": t0})
            return t0 + seconds

        if plan["mode"] == "open":
            t0 = time.monotonic()
            t_end = opened(t0)
            for i, q in enumerate(plan["requests"]):
                due = t0 + q["due_s"]
                delay = due - time.monotonic()
                if delay > 0:
                    await asyncio.sleep(delay)
                task = asyncio.ensure_future(start(i, due))
                tasks.add(task)
                task.add_done_callback(tasks.discard)
            await asyncio.sleep(max(0.0, t_end - time.monotonic()))
            say({"event": "closed", "t_start": t0, "t_end": t_end})
            window_tasks = set(tasks)
            if tail_s:
                # the same arrivals again, a window later, at the same
                # rate; they are no requests of the window (no due time,
                # a list of their own)
                over = asyncio.ensure_future(stopped(tail_s))
                for i, q in enumerate(plan["requests"]):
                    delay = t_end + q["due_s"] - time.monotonic()
                    if delay > 0:
                        await asyncio.wait({over}, timeout=delay)
                    if over.done():
                        break
                    task = asyncio.ensure_future(start(i, into=tail_recs))
                    tasks.add(task)
                    task.add_done_callback(tasks.discard)
                await over
            drained_at = t_end + plan["drain_s"]
            window_tasks = {t for t in window_tasks if not t.done()}
            if window_tasks:     # bounded drain, outside the window
                await asyncio.wait(window_tasks, timeout=max(
                    0.0, drained_at - time.monotonic()))
            for rec in recs:
                # a tail longer than the drain: what ended after the
                # drain's end was cancelled there without a tail
                if rec.get("end", 0.0) > drained_at:
                    rec["ok"] = False
                    del rec["end"]
        else:
            n = len(plan["requests"])
            cursor = iter(range(1 << 62))
            close_at = [float("inf")]       # set when the window opens
            streaming: set = set()
            all_streaming = asyncio.Event()

            # with a ramp caller i starts when caller i-1 has its first
            # token, so the lanes fill in sequence and not in a race
            ramp_max_s = plan.get("ramp_max_s", 0)
            turn = [asyncio.Event() for _ in range(plan["concurrency"] + 1)]
            for ev in turn[:1] if ramp_max_s else turn:
                ev.set()

            async def caller(me: int):
                def first_token():
                    streaming.add(me)
                    turn[me + 1].set()
                    if len(streaming) == plan["concurrency"]:
                        all_streaming.set()
                await turn[me].wait()
                while time.monotonic() < close_at[0]:
                    await start(next(cursor) % n, on_first=first_token)
            for me in range(plan["concurrency"]):
                tasks.add(asyncio.ensure_future(caller(me)))
            if ramp_max_s:
                # the window opens when every caller streams tokens (the
                # ramp is set-up), or after ramp_max_s at the latest
                try:
                    await asyncio.wait_for(all_streaming.wait(),
                                           timeout=ramp_max_s)
                except asyncio.TimeoutError:
                    pass
            t0 = time.monotonic()
            t_end = opened(t0)
            close_at[0] = t_end + tail_s   # the callers replay on in a tail
            await asyncio.sleep(max(0.0, t_end - time.monotonic()))
            say({"event": "closed", "t_start": t0, "t_end": t_end})
            if tail_s:
                await stopped(tail_s)
        unfinished = {t for t in tasks if not t.done()}
        for task in unfinished:
            task.cancel()
        if unfinished:
            await asyncio.wait(unfinished, timeout=30)
        keep = ("index", "due", "sent", "end", "ok", "error", "steps",
                "times", "in_range")
        return {"t_start": t0, "t_end": t_end, "mode": plan["mode"],
                "requests": [{k: r[k] for k in keep if k in r}
                             for r in recs],
                "tail_requests": [{k: r[k] for k in keep if k in r}
                                  for r in tail_recs]}


async def main() -> int:
    loop = asyncio.get_running_loop()
    client = Client()

    def say(obj: dict) -> None:
        sys.stdout.write(json.dumps(obj) + "\n")
        sys.stdout.flush()

    reading: list = []      # the one read of stdin in flight, if any

    def next_line():
        if not reading:
            reading.append(loop.run_in_executor(None, sys.stdin.readline))
        return reading[0]

    async def read() -> dict:
        line = await next_line()
        reading.clear()
        if not line:                # the parent is gone
            return {"op": "quit"}
        return json.loads(line)

    async def stopped_within(seconds: float) -> None:
        """The parent's next command (``stop``), or ``seconds``.  A read
        that timed out stays in flight for the main loop."""
        done, _ = await asyncio.wait({next_line()}, timeout=seconds)
        if done:
            early.append(await read())
    early: list = []        # what the parent said during a tail

    try:
        while True:
            cmd = early.pop(0) if early else await read()
            op = cmd["op"]
            try:
                if op == "quit":
                    return 0
                if op == "stop":    # the tail it ends is over already
                    continue
                if op == "connect":
                    await client.connect(cmd["port"], cmd.get("channels", 1))
                    say({"ok": True})
                elif op == "generate":
                    say(await client.op_generate(cmd))
                elif op == "window":
                    payloads = client.build_payloads(cmd)
                    say({"event": "ready"})
                    go = await read()
                    if go["op"] != "go":
                        return 0
                    say({"event": "done", "result": await client.run_window(
                        cmd, payloads, say, stopped_within)})
                else:
                    say({"error": f"unknown op {op!r}"})
            except Exception as e:  # noqa: BLE001 - reported to the parent, which fails the run
                say({"error": f"{type(e).__name__}: {e}"})
    finally:
        await client.close()


if __name__ == "__main__":
    sys.exit(asyncio.run(main()))
