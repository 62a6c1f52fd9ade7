"""The general traffic generator and the load generator's loops."""

import asyncio
import json
import os
import time

import pytest

from benchmark.lib import checkpoint, loadgen, traffic
from benchmark.lib.manifest import traffic_path

from . import helpers as H

MIXES = {"open": H.TINY_OPEN, "closed": H.TINY_CLOSED,
         "bursty": dict(H.TINY_OPEN, arrivals={"dist": "gamma", "cv": 3}),
         "sessions": dict(H.TINY_OPEN, sessions={
             "turns": {"dist": "uniform", "min": 2, "max": 4},
             "grow_tokens": {"dist": "uniform", "min": 5, "max": 9},
             "think_s": {"dist": "uniform", "min": 0.05, "max": 0.1}})}


def _key(sched):
    return [(r["due"], r["prompt_tokens"], r["output_tokens"])
            for r in sched["requests"]]


@pytest.mark.parametrize("mix", MIXES.values(), ids=MIXES.keys())
def test_same_seed_same_schedule_other_seed_other_order(mix):
    a = traffic.schedule(mix, 2**31 + 11, 20)
    b = traffic.schedule(mix, 2**31 + 11, 20)
    c = traffic.schedule(mix, 12, 20)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)
    # another seed is the same work in another order
    for field in ("prompt_tokens", "output_tokens"):
        assert sorted(r[field] for r in a["requests"] if r["due"] is None
                      or r["due"] >= 0) == \
            sorted(r[field] for r in c["requests"] if r["due"] is None
                   or r["due"] >= 0)


def test_seeds_give_independent_orders_of_the_same_work():
    """Every seed is a fresh shuffle of one multiset (not a rotation of
    one order): neighbours differ from seed to seed, and so many seeds
    give so many orders."""
    mix = dict(H.TINY_OPEN, rate_rps=5, preroll_s=0)
    sizes = lambda seed: [  # noqa: E731
        (r["prompt_tokens"], r["output_tokens"])
        for r in traffic.schedule(mix, seed, 20)["requests"]]
    a, b = sizes(0), sizes(2**31 + 30)
    assert sorted(a) != a and sorted(p for p, _ in a) == sorted(
        p for p, _ in b)
    rotations = [a[k:] + a[:k] for k in range(len(a))]
    assert b not in rotations
    assert len({tuple(sizes(s)) for s in range(200)}) == 200


def test_preroll_is_the_end_of_the_windows_own_cycle():
    """Every request of the window has a twin one window length
    earlier: with one whole cycle of pre-roll, every shape the window
    uses was used before it opened."""
    mix = dict(H.TINY_OPEN, rate_rps=5, preroll_s=0, preroll_cycles=1)
    reqs = traffic.schedule(mix, 9, 20)["requests"]
    win = [r for r in reqs if r["due"] >= 0]
    pre = [r for r in reqs if r["due"] < 0]
    assert len(win) == len(pre) == 100 and traffic.preroll(mix, 20) == 20
    for a, b in zip(pre, win):
        assert b["due"] - a["due"] == pytest.approx(20)
        assert (a["prompt_tokens"], a["output_tokens"]) == (
            b["prompt_tokens"], b["output_tokens"])
    assert len({r["id"] for r in reqs}) == 200
    short = traffic.schedule(dict(mix, preroll_cycles=0, preroll_s=2), 9, 20)
    assert all(r["due"] >= -2 for r in short["requests"])
    assert sum(1 for r in short["requests"] if r["due"] < 0) == sum(
        1 for r in win if r["due"] >= 18)


def test_open_loop_arrivals():
    mix = dict(H.TINY_OPEN, rate_rps=5, preroll_s=2)
    s = traffic.schedule(mix, 1, 20)
    due = [r["due"] for r in s["requests"]]
    assert due == sorted(due)
    assert sum(1 for d in due if 0 <= d < 20) == 100
    assert min(due) >= -2 and max(due) < 20
    gaps = sorted(b - a for a, b in zip(due, due[1:]))
    assert gaps[len(gaps) // 2] < 0.2 < gaps[-1]  # exponential, mean 0.2


def test_lengths_follow_the_distribution():
    d = {"dist": "lognormal", "median": 256, "sigma": 0.9, "min": 32,
         "max": 2048}
    q = traffic.quantiles(d, 201)
    assert q == sorted(q) and q[0] == 32 and q[-1] == 2048
    assert abs(q[100] - 256) < 1e-6
    assert traffic.quantiles({"dist": "fixed", "value": 7}, 3) == [7, 7, 7]
    u = traffic.quantiles({"dist": "uniform", "min": 128, "max": 512}, 4)
    assert u == [176.0, 272.0, 368.0, 464.0]


REAL_MIXES = sorted(f[:-5] for f in os.listdir(
    os.path.join(H.ROOT, "benchmark", "traffic")) if f.endswith(".json"))


@pytest.mark.parametrize("name", REAL_MIXES)
def test_every_mix_file_loads_and_says_where_its_sizes_come_from(name):
    mix = traffic.load_mix(traffic_path(H.ROOT, name))
    assert len(mix.get("notes") or "") > 40
    assert traffic.schedule(mix, 2**31 + 3, 51)["requests"]


@pytest.mark.parametrize("seed", [0, 7, 1234567891, 2**31 + 5])
@pytest.mark.parametrize("name", REAL_MIXES)
def test_every_mix_file_is_the_same_work_under_every_seed(name, seed):
    """What a cell's bound rests on, held for every committed mix and
    asserting only what the mix itself states: greedy requests, sizes
    inside its stated ranges, and in the window under another seed the
    same multiset of sizes in another order."""
    mix = traffic.load_mix(traffic_path(H.ROOT, name))
    assert mix["request"].get("temperature") == 0
    a, b = ([(r["prompt_tokens"], r["output_tokens"])
             for r in traffic.schedule(mix, s, 51)["requests"]
             if r["due"] is None or r["due"] >= 0] for s in (seed, seed + 1))
    assert a != b and len(a) == len(b)
    for k, field in enumerate(("prompt_tokens", "output_tokens")):
        assert sorted(r[k] for r in a) == sorted(r[k] for r in b)
        dist = mix[field]
        if "sessions" in mix or "burst" in mix or dist["dist"] == "lognormal":
            continue  # turns grow, bursts bring sizes of their own
        assert dist.get("min", dist.get("value")) <= min(r[k] for r in a)
        assert max(r[k] for r in a) <= dist.get("max", dist.get("value"))


@pytest.mark.parametrize("bad,msg", [
    (dict(H.TINY_OPEN, flavour="x"), "unknown traffic fields"),
    ({k: v for k, v in H.TINY_OPEN.items() if k != "rate_rps"}, "rate_rps"),
    (dict(H.TINY_CLOSED, clients=0), "clients"),
    (dict(H.TINY_OPEN, loop="spiral"), "open or closed"),
    (dict(H.TINY_CLOSED, burst={"every_s": 1}), "open-loop"),
    (dict(H.TINY_CLOSED, warm_episode_s=-1), "warm_episode_s"),
])
def test_mix_errors(bad, msg):
    with pytest.raises(traffic.MixError, match=msg):
        traffic.check_mix(bad)


def test_cell_file_overrides_the_rate(tmp_path):
    p = tmp_path / "m.json"
    p.write_text(json.dumps({k: v for k, v in H.TINY_OPEN.items()
                             if k != "rate_rps"}))
    with pytest.raises(traffic.MixError):
        traffic.load_mix(str(p))
    assert traffic.load_mix(str(p), {"rate_rps": 2.5})["rate_rps"] == 2.5


@pytest.fixture(scope="module")
def tokenizer_json(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("tk"))
    checkpoint.build_bpe_tokenizer(d, 512)
    return os.path.join(d, "tokenizer.json")


def test_prompts_have_the_wanted_length_and_share_nothing(tokenizer_json):
    pm = traffic.PromptMaker(tokenizer_json, 5, stream_tokens=5000)
    a, b = pm.text(200, "r1"), pm.text(200, "r2")
    assert abs(pm.count(a) - 200) <= 12 and abs(pm.count(b) - 200) <= 12
    assert a[:4] != b[:4]
    again = traffic.PromptMaker(tokenizer_json, 5, stream_tokens=5000)
    assert again.text(200, "r1") == a
    other = traffic.PromptMaker(tokenizer_json, 6, stream_tokens=5000)
    assert other.text(200, "r1") != a
    s1, s2 = pm.text(300, "r3", shared=100), pm.text(300, "r4", shared=100)
    n = len(os.path.commonprefix([s1, s2]))
    assert pm.count(s1[:n]) >= 95
    assert pm.grow(a, 20, "t1").startswith(a)


# ---- the loops, against a stand-in SSE server ----------------------------


class FakeServer:
    """Streams ``max_tokens`` chunks, two per write, ``step`` apart;
    counts how many requests are open at once."""

    def __init__(self, step=0.01):
        self.step, self.open, self.max_open, self.seen = step, 0, 0, 0

    async def chat(self, request):
        from aiohttp import web

        body = await request.json()
        self.open += 1
        self.seen += 1
        self.max_open = max(self.max_open, self.open)
        resp = web.StreamResponse(
            headers={"Content-Type": "text/event-stream"})
        await resp.prepare(request)

        def ev(delta, finish=None, usage=None):
            d = {"choices": [{"index": 0, "delta": delta,
                              "finish_reason": finish}]}
            if usage:
                d["usage"] = usage
            return f"data: {json.dumps(d)}\n\n".encode()

        await resp.write(ev({"role": "assistant", "content": ""}))
        n = body["max_tokens"]
        try:
            for i in range(0, n, 2):
                await asyncio.sleep(self.step)
                await resp.write(b"".join(ev({"content": "ab"})
                                          for _ in range(min(2, n - i))))
            await resp.write(ev({}, "length", {
                "completion_tokens": n, "prompt_tokens": 9}))
            await resp.write(b"data: [DONE]\n\n")
        finally:
            self.open -= 1
        return resp


class WordPrompts:
    def text(self, n, tag, shared=0):
        return f"[{tag}] " + "w " * n

    def grow(self, prompt, n, tag):
        return prompt + f" [{tag}] " + "w " * n


async def _drive(mix, seconds, fake):
    from aiohttp import web
    from aiohttp.test_utils import TestServer

    app = web.Application()
    app.router.add_post("/v1/chat/completions", fake.chat)
    async with TestServer(app) as ts:
        sched = traffic.schedule(mix, 4, seconds)
        clock = loadgen.Clock(time.perf_counter()
                              + mix.get("preroll_s", 0) + 0.05)
        base = f"http://{ts.host}:{ts.port}"
        return await loadgen.run_schedule(
            base, "m", mix, sched, WordPrompts(), clock, seconds)


def test_open_loop_times_from_due_and_all_requests_are_served():
    mix = dict(H.TINY_OPEN, rate_rps=20, preroll_s=0.2, drain_s=5)
    fake = FakeServer()
    log = asyncio.run(_drive(mix, 1.0, fake))
    assert len(log) == fake.seen and 21 <= len(log) <= 28
    for r in log:
        assert loadgen.malformed(r) is None, r
        assert r["sent"] >= r["due"] - 1e-4
        assert r["chunk_t"][0] > r["due"]
        assert r["completion_tokens"] == r["output_tokens"]
        assert len(r["chunk_t"]) == r["output_tokens"]
    assert sum(1 for r in log if 0 <= r["due"] < 1.0) == 20


def test_closed_loop_keeps_n_in_flight():
    mix = dict(H.TINY_CLOSED, clients=3, preroll_s=0.2, ramp_s=0.1,
               drain_s=5)
    fake = FakeServer(step=0.02)
    log = asyncio.run(_drive(mix, 1.0, fake))
    assert fake.max_open == 3
    assert len(log) >= 9 and all(loadgen.malformed(r) is None for r in log)
    by_client: dict = {}
    for r in log:
        by_client.setdefault(r["tag"].split("n")[0], []).append(r)
    assert len(by_client) == 3
    for recs in by_client.values():  # next request when the reply ends
        for a, b in zip(recs, recs[1:]):
            assert 0 <= b["sent"] - a["end"] < 0.05


def test_sessions_send_the_next_turn_after_the_reply():
    mix = dict(MIXES["sessions"], rate_rps=10, preroll_s=0, drain_s=5)
    log = asyncio.run(_drive(mix, 1.0, FakeServer()))
    turns = [r for r in log if "t" in r["tag"][1:]]
    assert turns
    first = {r["tag"]: r for r in log}
    for r in turns:
        head = r["tag"].split("t")[0]
        assert r["due"] >= first[head]["end"]
        assert r["prompt_tokens"] > first[head]["prompt_tokens"]


def test_a_malformed_reply_counts_as_failed():
    rec = {"status": 200, "done": True, "finish_reason": "stop",
           "completion_tokens": 8, "output_tokens": 8, "chunk_t": [0.1]}
    assert "finish_reason" in loadgen.malformed(rec)
    assert "asked for" in loadgen.malformed(
        dict(rec, finish_reason="length", completion_tokens=7))
    assert loadgen.malformed(dict(rec, status=429)) == "HTTP 429"
    assert loadgen.malformed(dict(rec, finish_reason="length")) is None
    assert loadgen.malformed(dict(rec, finish_reason="length",
                                  chunk_t=[])) is None
