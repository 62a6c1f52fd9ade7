"""The load generator: one process, one asyncio loop, streamed requests.

Sends a schedule (lib/traffic.py) to the server and keeps a request log:
for every request the time it was due, the time it was sent, the arrival
time of every content chunk, and how it ended. All times are seconds
from the start of the measured window on ``time.perf_counter()``.

An open-loop request is timed from when it was DUE, so a stall is paid
by every request behind it; how late the generator itself ran (sent -
due) is in the log. A closed-loop client sends its next request when
its reply ends; its ``due`` is that moment.
"""

from __future__ import annotations

import asyncio
import json
import time


class Clock:
    """Window-relative time: 0 is the start of the measured window."""

    def __init__(self, t0_abs: float) -> None:
        self.t0 = t0_abs

    def now(self) -> float:
        return time.perf_counter() - self.t0


def _body(mix: dict, model: str, text: str, max_tokens: int) -> dict:
    body = dict(mix.get("request") or {})
    body.update(model=model, max_tokens=int(max_tokens), stream=True,
                stream_options={"include_usage": True})
    if mix["endpoint"].endswith("/chat/completions"):
        body["messages"] = [{"role": "user", "content": text}]
    else:
        body["prompt"] = text
    return body


async def stream_request(session, base: str, endpoint: str, body: dict,
                         clock: Clock, rec: dict, timeout_s: float) -> str:
    """One streamed request; fills ``rec``; returns the streamed text."""
    import aiohttp

    rec["sent"] = clock.now()
    rec.update(chunk_t=[], status=0, finish_reason=None,
               completion_tokens=None, prompt_tokens_served=None,
               done=False, error=None, end=None)
    text = []
    try:
        async with session.post(
                base + endpoint, json=body,
                timeout=aiohttp.ClientTimeout(total=timeout_s)) as resp:
            rec["status"] = resp.status
            if resp.status != 200:
                rec["error"] = (await resp.text())[:300]
                return ""
            async for raw in resp.content:
                if not raw.startswith(b"data:"):
                    continue
                data = raw[5:].strip()
                if data == b"[DONE]":
                    rec["done"] = True
                    break
                ev = json.loads(data)
                if ev.get("error"):
                    rec["error"] = str(ev["error"])[:300]
                ch = (ev.get("choices") or [{}])[0]
                piece = ((ch.get("delta") or {}).get("content")
                         or ch.get("text") or "")
                if piece:
                    rec["chunk_t"].append(clock.now())
                    text.append(piece)
                if ch.get("finish_reason"):
                    rec["finish_reason"] = ch["finish_reason"]
                if ev.get("usage"):
                    rec["completion_tokens"] = ev["usage"].get(
                        "completion_tokens")
                    rec["prompt_tokens_served"] = ev["usage"].get(
                        "prompt_tokens")
    except (aiohttp.ClientError, asyncio.TimeoutError, OSError,
            ValueError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:300]
    finally:
        rec["end"] = clock.now()
    return "".join(text)


def malformed(rec: dict) -> "str | None":
    """Why a finished request does not count as served, or None. A
    reply with the asked-for token count and no visible text is served
    (greedy decoding on random weights can repeat a lone UTF-8
    continuation byte, which the server's decoder rightly holds back);
    it has no first-chunk time and adds no latency sample."""
    if rec.get("error"):
        return rec["error"]
    if rec.get("status") != 200:
        return f"HTTP {rec.get('status')}"
    if not rec.get("done"):
        return "no [DONE]"
    if rec.get("finish_reason") != "length":
        return f"finish_reason {rec.get('finish_reason')!r}"
    if rec.get("completion_tokens") != rec.get("output_tokens"):
        return (f"{rec.get('completion_tokens')} completion tokens, asked "
                f"for {rec.get('output_tokens')}")
    return None


async def run_schedule(base: str, model: str, mix: dict, sched: dict,
                       prompts, clock: Clock, seconds: float,
                       side_tasks=(), timeout_s: float = 180.0) -> list:
    """Drive ``sched`` against the server until the window closes, then
    drain. ``prompts`` is a lib.traffic.PromptMaker. ``side_tasks`` are
    coroutine functions (clock) -> None run beside the load (the 1 Hz
    poller, the profile capture). -> the request log, a list of dicts."""
    import aiohttp

    log: list = []
    drain_s = float(mix.get("drain_s", 30.0))
    endpoint = mix["endpoint"]
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(connector=conn) as session:

        async def one(req: dict, due: float, tag: str, text=None) -> tuple:
            rec = {"id": req["id"], "tag": tag, "due": due,
                   "prompt_tokens": req["prompt_tokens"],
                   "output_tokens": req["output_tokens"]}
            log.append(rec)
            if text is None:
                text = prompts.text(req["prompt_tokens"], tag,
                                    req.get("shared_prefix_tokens", 0))
            out = await stream_request(
                session, base, endpoint,
                _body(mix, model, text, req["output_tokens"]), clock, rec,
                timeout_s)
            return text, out

        async def open_request(req: dict) -> None:
            tag = f"r{req['id']}"
            text, _ = await one(req, req["due"], tag)
            # a session: each further turn is due think_s after the reply
            for t in range(1, int(req.get("turns", 1))):
                due = clock.now() + req["think_s"][t]
                if due >= seconds:
                    return
                await asyncio.sleep(max(0.0, due - clock.now()))
                grow = req["grow_tokens"][t]
                text = prompts.grow(text, grow, f"{tag}t{t}")
                turn = dict(req, prompt_tokens=req["prompt_tokens"] + grow)
                req = turn
                text, _ = await one(turn, due, f"{tag}t{t}", text)

        async def open_loop() -> list:
            tasks = []
            for req in sched["requests"]:
                delay = req["due"] - clock.now()
                if delay > 0:
                    await asyncio.sleep(delay)
                tasks.append(asyncio.create_task(open_request(req)))
            return tasks

        async def closed_client(i: int, queue: list) -> None:
            await asyncio.sleep(max(0.0, sched["starts"][i] - clock.now()))
            while clock.now() < seconds:
                n = queue[0]
                queue[0] += 1
                reqs = sched["requests"]
                req = dict(reqs[n % len(reqs)], id=n)
                await one(req, clock.now(), f"c{i}n{n}")

        sides = [asyncio.create_task(fn(clock)) for fn in side_tasks]
        if sched["loop"] == "open":
            tasks = await open_loop()
        else:
            queue = [0]
            tasks = [asyncio.create_task(closed_client(i, queue))
                     for i in range(sched["clients"])]
        await asyncio.sleep(max(0.0, seconds - clock.now()))
        if tasks:
            _, pending = await asyncio.wait(tasks, timeout=drain_s)
            for t in pending:
                t.cancel()
            await asyncio.gather(*pending, return_exceptions=True)
        for t in tasks:  # a bug in the generator is not a quiet failure
            if t.done() and not t.cancelled() and t.exception():
                raise t.exception()
        await asyncio.gather(*sides)
    return log
