#!/usr/bin/env python3
"""One run of one benchmark cell over the HTTP serving path.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for. One run = one new process:

  gate        refuse (exit 3, no result line) unless JAX, asked in a
              child, reports platform "tpu" with the cell's chip count
  checkpoint  the configuration's seeded checkpoint + tokenizer + model
              YAML under benchmark/.cache/, unless already there
  server      ``python -m localai_tfp_tpu.server`` as a child; the first
              request loads the model and warms up (set-up)
  probes      parity with the plain numpy reference through
              /v1/embeddings; a ~3000-token prompt and its repeat
  warm        where the mix asks for it (``warm_episode_s``), the cell's
              own traffic once from its start, drained: set-up
  window      the cell's traffic for --seconds (plus its pre-roll,
              which is set-up), /metrics before and after, 1 Hz polls;
              with --trace 1 a profiler capture at the window's end
  result      SIGTERM the server, require a clean exit, reduce, print
              ONE JSON object as the last line of stdout

``--seed`` seeds the traffic (order of lengths and gaps, prompt text).
The weights' seed is a field of the configuration file, so a checkout's
first run of a cell writes what every later run reuses. This process
never imports JAX while the server lives; the capture is reduced in a
child with JAX_PLATFORMS=cpu after the server has exited.
"""

from __future__ import annotations

import argparse
import asyncio
import glob
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import checkpoint, layer_metrics, loadgen  # noqa: E402
from benchmark.lib import manifest as M  # noqa: E402
from benchmark.lib import models  # noqa: E402
from benchmark.lib import peaks as P  # noqa: E402
from benchmark.lib import prom, reference, traffic  # noqa: E402
from benchmark.lib import reduce as R  # noqa: E402
from benchmark.lib.children import (  # noqa: E402
    CHILDREN, HarnessFailure, Server, probe_device,
)

EXPECT_CHIP = {"platform": "tpu", "attention_path": "ragged_paged_kernel"}
# what a configuration file's ``expect`` may lay over the caller's
EXPECT_KEYS = {"attention_path", "kernel_ineligible"}
LONG_PROMPT_TOKENS = 3000


def say(*a) -> None:
    """Everything but the result line: stdout, earlier lines."""
    print(*a, flush=True)


# ------------------------------------------------------------------ set-up


def compile_cache_dir(root: str) -> str:
    """Where the program keeps compiled code (its own rule, restated:
    localai_tfp_tpu/utils/compile_cache.py)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")


def cache_entries(path: str) -> int:
    try:
        return sum(1 for n in os.listdir(path) if not n.endswith(".json"))
    except OSError:
        return 0


def parity_reference(cache_dir: str, model: dict, config: dict,
                     config_path: str) -> list:
    """Mean-pooled final hidden state of each parity prompt by the plain
    reference, cached by content hash of the configuration file."""
    key = checkpoint.config_key(config_path)
    path = os.path.join(cache_dir, "reference", f"{key}.json")
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)["pooled"]
    from tokenizers import Tokenizer

    t0 = time.monotonic()
    tk = Tokenizer.from_file(os.path.join(model["ckpt_dir"],
                                          "tokenizer.json"))
    hf = checkpoint.hf_config(config)
    bos = hf["vocab_size"] - 2  # the tokenizer's <s> (lib/checkpoint.py)
    ids = [[bos] + tk.encode(t, add_special_tokens=False).ids
           for t in config["parity_prompts"]]
    pooled = [v.tolist() for v in reference.pooled(
        model["ckpt_dir"], hf, ids)]
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path + ".tmp", "w") as f:
        json.dump({"tokens": [len(i) for i in ids], "pooled": pooled}, f)
    os.replace(path + ".tmp", path)
    say(f"reference: {len(ids)} prompts of {[len(i) for i in ids]} tokens "
        f"in {time.monotonic() - t0:.1f}s")
    return pooled


def parity_probe(srv: Server, name: str, config: dict, want: list,
                 first_timeout: float) -> dict:
    errs = []
    for i, text in enumerate(config["parity_prompts"]):
        status, body = srv.post(
            "/v1/embeddings", {"model": name, "input": text},
            first_timeout if i == 0 else 300)
        if status != 200:
            raise HarnessFailure(
                f"/v1/embeddings -> HTTP {status}: {str(body)[:400]}\n"
                f"{srv.log_tail()}")
        errs.append(reference.rel_l2(body["data"][0]["embedding"], want[i]))
    tol = float(config["parity_tol"])
    return {"rel_l2": errs, "tol": tol, "ok": max(errs) < tol}


def cache_probe(srv: Server, name: str, long_prompt: str) -> dict:
    """One ~3000-token greedy prompt served alone, then repeated alone:
    equal text, and at least a page of it served from resident pages."""
    def reused() -> float:
        return prom.total(prom.parse(srv.get("/metrics").decode()),
                          "engine_prefix_reused_tokens_total")

    body = {"model": name, "max_tokens": 8, "temperature": 0,
            "ignore_eos": True, "prompt": long_prompt}
    r0 = reused()
    s1, b1 = srv.post("/v1/completions", body, 300)
    r1 = reused()
    s2, b2 = srv.post("/v1/completions", body, 300)
    r2 = reused()
    if s1 != 200 or s2 != 200:
        return {"ok": False, "why": f"HTTP {s1}/{s2}: {str(b1)[:200]}"}
    t1, t2 = b1["choices"][0]["text"], b2["choices"][0]["text"]
    out = {"prompt_tokens": b1["usage"]["prompt_tokens"],
           "reused_first": r1 - r0, "reused_repeat": r2 - r1,
           "text_equal": t1 == t2}
    out["ok"] = bool(out["text_equal"] and out["reused_repeat"] >= 256)
    return out


# ------------------------------------------------------------------ window


async def _window(srv: Server, name: str, mix: dict, sched: dict,
                  prompts, seconds: float, trace: bool, cc_dir: str,
                  t0_abs: float) -> dict:
    import aiohttp

    clock = loadgen.Clock(t0_abs)
    got: dict = {"polls": [], "profile": None}

    async def scrape(session) -> dict:
        async with session.get(srv.base + "/metrics") as r:
            return prom.parse(await r.text())

    async def bookends(clock) -> None:
        async with aiohttp.ClientSession() as s:
            await asyncio.sleep(max(0.0, -clock.now()))
            got["cc_before"] = cache_entries(cc_dir)
            got["log_before"] = os.path.getsize(srv.log_path)
            got["metrics_before"] = await scrape(s)
            while clock.now() < seconds - 1.0:
                await asyncio.sleep(1.0)
                got["polls"].append(await scrape(s))
            await asyncio.sleep(max(0.0, seconds - clock.now()))
            got["metrics_after"] = await scrape(s)
            got["cc_after"] = cache_entries(cc_dir)
            got["log_after"] = os.path.getsize(srv.log_path)

    async def capture(clock) -> None:
        dur = max(1.0, min(3.0, 0.3 * seconds))
        async with aiohttp.ClientSession() as s:
            # at the END of the window: stopping the profiler holds the
            # server's event loop for tens of seconds while it writes
            # the capture, and that stall should fall after the window
            await asyncio.sleep(max(0.0, seconds - dur - 0.5 - clock.now()))
            before, t_b = await scrape(s), clock.now()
            async with s.get(srv.base + f"/debug/profile?duration={dur}",
                             timeout=aiohttp.ClientTimeout(total=120)) as r:
                body = await r.text()
                if r.status != 200:
                    say(f"profile: HTTP {r.status}: {body[:300]}")
                    return
            after, t_a = await scrape(s), clock.now()
            got["profile"] = {"before": before, "after": after,
                              "t_before": t_b, "t_after": t_a,
                              "duration": dur,
                              "path": json.loads(body).get("path")}

    sides = [bookends] + ([capture] if trace else [])
    got["log"] = await loadgen.run_schedule(
        srv.base, name, mix, sched, prompts, clock, seconds, sides)
    return got


def reduce_trace(root: str, profile: "dict | None", run_dir: str):
    """The capture -> the dumped device events (lib/trace.py), in a
    child off the chip. None when there is no capture."""
    if not profile or not profile.get("path"):
        return None
    found = glob.glob(os.path.join(profile["path"], "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        say(f"trace: no .xplane.pb under {profile['path']}")
        return None
    out = os.path.join(run_dir, "trace.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root)
    env.pop("BENCH_RUN", None)
    proc = CHILDREN.spawn(
        [sys.executable, os.path.join(root, "benchmark", "lib", "trace.py"),
         "dump", found[0], out],
        cwd=root, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    text, _ = proc.communicate(timeout=300)
    if proc.returncode != 0:
        say(f"trace: dump failed rc={proc.returncode}: {text[-800:]}")
        return None
    from benchmark.lib import trace as T

    with open(out) as f:
        tr = json.load(f)
    if not T.chip_planes(tr):
        say(f"trace: no device plane in the capture (planes: "
            f"{tr.get('other_planes')})")
        return None
    return tr


# --------------------------------------------------------------------- run


class Cell:
    """A cell brought up to the edge of its window: files read, the
    checkpoint there, the server child loaded, warmed and probed."""

    def __init__(self, root: str, cell_name: str, seed: int, trace: bool,
                 tag: str, expect: dict = EXPECT_CHIP,
                 probe: bool = True) -> None:
        self.root, self.seed, self.trace = root, seed, trace
        # a model type is a file of the checkout's own, like a metric
        models.use(os.path.join(M.bench_dir(root), "models"))
        self.man = M.load(root)
        bad = M.problems(self.man, root)
        if bad:
            raise HarnessFailure("BENCHMARK.json: " + "; ".join(bad[:6]))
        self.cell = M.cell(self.man, cell_name)
        self.name = self.cell["config"]
        self.cfg_path = M.config_path(self.man, root, self.name)
        with open(self.cfg_path) as f:
            self.config = json.load(f)
        # the engine state `correct` expects: the caller's, and over it
        # what the configuration states for its own engine path
        own = self.config.get("expect") or {}
        unknown = sorted(set(own) - EXPECT_KEYS)
        if unknown:
            raise HarnessFailure(f"{self.cfg_path}: expect has {unknown}; "
                                 f"it may state {sorted(EXPECT_KEYS)}")
        self.expect = dict(expect, **own)
        self.mix = traffic.load_mix(
            M.traffic_path(root, self.cell["traffic"]),
            M.cell_overrides(root, cell_name))
        self.cache_dir = os.path.join(M.bench_dir(root), ".cache")
        self.run_dir = os.path.join(self.cache_dir, "runs", tag)
        shutil.rmtree(self.run_dir, ignore_errors=True)
        os.makedirs(self.run_dir)
        self.fails: list = []  # clauses of `correct` that failed, by name
        self.probe = probe
        self.srv = None

    def bring_up(self) -> None:
        root, name, expect = self.root, self.name, self.expect
        # gate: a first run in a checkout asks JAX in a child before it
        # writes gigabytes; every run checks the server's own devices
        marker = os.path.join(
            self.cache_dir, "models",
            f"{name}-{checkpoint.config_key(self.cfg_path)}", "marker.json")
        if self.probe and not os.path.exists(marker):
            dev = probe_device(root)
            if dev["platform"] != expect["platform"] \
                    or dev["count"] < self.cell["chips"]:
                raise HarnessFailure(
                    f"JAX found {dev['count']} x {dev['platform']} "
                    f"({dev['kind']}); the cell needs "
                    f"{self.cell['chips']} x {expect['platform']}")
        model = checkpoint.materialise(self.cache_dir, name, self.cfg_path,
                                       log=say)
        self.cc_dir = compile_cache_dir(root)
        say(f"cell {self.cell['name']}: config {name}, traffic "
            f"{self.cell['traffic']}, seed {self.seed}, trace "
            f"{int(self.trace)}; compile cache {self.cc_dir} "
            f"({cache_entries(self.cc_dir)} entries)")
        self.srv = srv = Server(
            root, model["home"], model["models_dir"],
            os.path.join(self.run_dir, "state"),
            os.path.join(self.run_dir, "server.log"),
            # JAX names every program it traces or compiles in the log,
            # which is how a compile inside the window is seen
            dict({"JAX_LOG_COMPILES": "1"},
                 **({"LOCALAI_PROFILER": "on"} if self.trace else {})))
        # the reference (first run only: cached after) and the prompt
        # stream are made while the server starts
        want_box: dict = {}
        th = threading.Thread(target=lambda: want_box.update(
            pooled=parity_reference(self.cache_dir, model, self.config,
                                    self.cfg_path)))
        th.start()
        tk_json = os.path.join(model["ckpt_dir"], "tokenizer.json")
        self.prompts = traffic.PromptMaker(tk_json, self.seed)
        long_prompt = traffic.PromptMaker(tk_json, 0, 8000).text(
            LONG_PROMPT_TOKENS, "long")
        srv.wait_ready(180)
        self.devices = srv.get_json("/system").get("devices") or []
        if len(self.devices) < self.cell["chips"] or not any(
                expect["platform"] in d.lower() for d in self.devices):
            raise HarnessFailure(
                f"the server's JAX reports {self.devices}; the cell needs "
                f"{self.cell['chips']} x {expect['platform']}")
        th.join()
        if "pooled" not in want_box:
            raise HarnessFailure("the reference could not be computed")
        # the first request loads the model: read, quantize, transfer,
        # the whole warmup compile pass on a cold cache
        parity = parity_probe(srv, name, self.config, want_box["pooled"],
                              1100)
        say("parity: rel L2 " + " ".join(f"{e:.2e}" for e in
                                         parity["rel_l2"])
            + f" (tol {parity['tol']})")
        if not parity["ok"]:
            self.fails.append("parity")
        mon = self.monitor()
        eng = mon.get("engine") or {}
        say("load: " + json.dumps(mon.get("load_breakdown")))
        if eng.get("platform") != expect["platform"]:
            raise HarnessFailure(
                f"the engine reports platform {eng.get('platform')!r}, "
                f"not {expect['platform']!r}")
        if eng.get("attention_path") != expect["attention_path"] \
                or (eng.get("kernel_ineligible") or "") != expect.get(
                    "kernel_ineligible", ""):
            self.fails.append("attention_path")
            say(f"attention_path {eng.get('attention_path')!r}, "
                f"kernel_ineligible {eng.get('kernel_ineligible')!r}")
        cp = cache_probe(srv, name, long_prompt)
        say("cache probe: " + json.dumps(cp))
        if not cp["ok"]:
            self.fails.append("cache_path")

    def log_size(self) -> int:
        return os.path.getsize(self.srv.log_path)

    def compiles_between(self, lo: int, hi: int) -> list:
        """Programs JAX traced or compiled while the server's log grew
        from byte ``lo`` to ``hi`` (JAX_LOG_COMPILES lines)."""
        with open(self.srv.log_path, "rb") as f:
            f.seek(lo)
            text = f.read(max(0, hi - lo)).decode(errors="replace")
        # the server logs each record twice (root handler + JAX's own)
        return sorted({ln.split("Compiling ", 1)[1].split(" ", 1)[0]
                       + " @" + ln[:23]
                       for ln in text.splitlines()
                       if " Compiling " in ln and "WARNING:" not in ln[:8]})

    def monitor(self) -> dict:
        return self.srv.get_json(f"/backend/monitor?model={self.name}")

    def window(self, mix: dict, seed: int, seconds: float,
               trace: bool = False) -> "tuple[dict, float]":
        """Run one window of ``mix``; -> (what was collected, the
        absolute perf_counter time the window opened)."""
        sched = traffic.schedule(mix, seed, seconds)
        t0_abs = time.perf_counter() + traffic.preroll(mix, seconds) + 0.25
        got = asyncio.run(_window(self.srv, self.name, mix, sched,
                                  self.prompts, float(seconds), trace,
                                  self.cc_dir, t0_abs))
        return got, t0_abs

    def warm_episode(self) -> "dict | None":
        """The mix's ``warm_episode_s``: the cell's own traffic (same
        seed, so the same sizes in the same order) from its start,
        drained, before the measured episode begins. On a warm start
        the engine skips its warmup pass and traces and loads each
        dispatch variant on first use inside the serving loop — seconds
        in which every stream stands still — and which variants a
        traffic reaches, and when, is its own business: so it is run
        once through, and the measured episode finds them loaded."""
        seconds = float(self.mix.get("warm_episode_s") or 0.0)
        if seconds <= 0:
            return None
        mix = dict(self.mix, preroll_s=0, preroll_cycles=0)
        lo, t0 = self.log_size(), time.perf_counter()
        got, _ = self.window(mix, self.seed, seconds)
        bad = [r["tag"] for r in got["log"] if loadgen.malformed(r)]
        loads = self.compiles_between(lo, self.log_size())
        out = {"seconds": seconds, "took_s": time.perf_counter() - t0,
               "requests": len(got["log"]), "malformed": len(bad),
               "first_use_loads": len(loads)}
        say("warm episode: " + json.dumps(out) + " " + ", ".join(loads[:12]))
        if bad:
            self.fails.append("responses")
        return out

    def shut_down(self) -> "tuple[dict, dict]":
        """-> (final monitor, final /metrics); the server must exit 0."""
        mon = self.monitor()
        final = prom.parse(self.srv.get("/metrics").decode())
        rc = self.srv.stop()
        if rc != 0:
            self.fails.append("server_exit")
            say(f"server exit code {rc}: {self.srv.log_tail(1500)}")
        return mon, final


def run_cell(root: str, cell_name: str, seed: int, seconds: float,
             trace: bool, t_start: float, expect: dict = EXPECT_CHIP,
             probe: bool = True) -> dict:
    """Everything after argument parsing; -> the result object. Raises
    HarnessFailure when no result can be given."""
    c = Cell(root, cell_name, seed, trace,
             f"{cell_name}-seed{seed}-trace{int(trace)}", expect, probe)
    try:
        c.bring_up()
        warm = c.warm_episode()
        got, t0_abs = c.window(c.mix, seed, seconds, trace)
        setup_s = t0_abs - t_start
        mon, final = c.shut_down()
    finally:
        CHILDREN.stop_all()
    man, config, mix, cell, fails = c.man, c.config, c.mix, c.cell, c.fails
    run_dir, devices = c.run_dir, c.devices

    log = got["log"]
    with open(os.path.join(run_dir, "requests.jsonl"), "w") as f:
        for r in log:
            f.write(json.dumps(r) + "\n")
    win = R.in_window(log, seconds)
    bad_reqs = [(r["tag"], loadgen.malformed(r)) for r in win
                if loadgen.malformed(r)]
    pre_bad = [r["tag"] for r in log if r not in win
               and r["due"] is not None and r["due"] < 0
               and loadgen.malformed(r)]
    for tag, why in bad_reqs[:5]:
        say(f"request {tag}: {why}")
    if (bad_reqs or pre_bad) and "responses" not in fails:
        fails.append("responses")
    if prom.total(final, "engine_requests_total", {"reason": "error"}) > 0:
        fails.append("engine_errors")
    compiled = c.compiles_between(got.get("log_before", 0),
                                  got.get("log_after", 0))
    # what was compiled, or traced and loaded from the persistent cache,
    # inside the window is named here and decides nothing by itself: it
    # is a fault of the measurement, which shows in the numbers (a
    # checkout's first runs), not of the program's outputs. On a warm
    # start the engine skips its warmup pass (warmup_reused) and loads
    # each variant on first use; which ones a window first touches
    # hangs on timing. `correct` keeps the ISSUE's clause: the engine's
    # variants gauge did not grow
    grew = got.get("cc_after", 0) - got.get("cc_before", 0)
    if compiled or grew:
        say(f"inside the window: compile cache +{grew} entries; "
            f"{len(compiled)} programs traced and loaded on first use: "
            + ", ".join(compiled[:12]))
    variants = [prom.total(got[k], "engine_dispatch_compile_variants_count")
                for k in ("metrics_before", "metrics_after")]
    if variants[1] > variants[0]:
        fails.append("variants_grew")

    eng = mon.get("engine") or {}
    device = {"platform": eng.get("platform"),
              "kind": eng.get("device_kind"), "count": len(devices),
              "memory_peak_bytes": (eng.get("hbm") or {}).get(
                  "peak_bytes_in_use")}
    late = R.series(log, seconds, "late_ms")
    say(f"requests: {len(log)} sent, {len(win)} due in the window, "
        f"{len(bad_reqs)} failed; generator late p95 "
        f"{R.percentile(late, 95) if late else 0:.2f} ms; prompt tokens "
        + json.dumps(R.histogram(
            [r.get("prompt_tokens_served") or 0 for r in win],
            [64, 128, 256, 512, 1024, 2048]))
        + "; output tokens " + json.dumps(R.histogram(
            [r.get("completion_tokens") or 0 for r in win],
            [32, 64, 128, 256, 384])))

    metrics: dict = {}
    result = {"correct": not fails, "attempted": len(win),
              "failed": len(bad_reqs), "metrics": metrics,
              "device": device, "failed_clauses": fails,
              "compiled_in_window": {"cache_entries": grew,
                                     "first_use_loads": len(compiled)},
              "warm_episode": warm}
    if not trace:
        for m in M.metrics_of(man, "end_to_end", cell_name):
            v, n = R.end_to_end(M.end_to_end_spec(root, m["name"]), log,
                                float(seconds), setup_s)
            say(f"{m['name']}: {v} {m['unit']} over {n} samples")
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        from benchmark.lib import trace as T

        tr = reduce_trace(root, got.get("profile"), run_dir)
        run = {"config": config, "mix": mix, "cell": cell,
               "seconds": float(seconds), "log": log, "monitor": mon,
               "first_use_loads": len(compiled),
               "metrics_before": got.get("metrics_before"),
               "metrics_after": got.get("metrics_after"),
               "polls": got["polls"], "profile": got.get("profile"),
               "peaks": P.peaks(device["kind"])
               if device["platform"] == "tpu" else None}
        mdir = os.path.join(M.bench_dir(root), "layer_metrics")
        for m in M.metrics_of(man, "per_layer", cell_name):
            v = layer_metrics.evaluate(mdir, m["name"], tr, run)
            say(f"{m['name']}: {v} {m['unit']}")
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if tr is not None:
            busy, window = T.busy_and_window(tr)
            device.update(busy_s=busy, window_s=window)
            result["breakdown"] = {
                "device_ops": T.top_ops(tr, 10),
                "idle_gaps": T.idle_gaps(tr, 5)
                + [["sum_" + k, v] for k, v in
                   T.idle_by_neighbours(tr, 5)]}
    if fails:
        say("correct: false — " + ", ".join(fails))
    with open(os.path.join(run_dir, "result.json"), "w") as f:
        json.dump(result, f)
    return result


def _on_signal(signum, frame):
    CHILDREN.stop_all()
    raise SystemExit(128 + signum)


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    if not os.path.isdir(os.path.join(ROOT, "localai_tfp_tpu")):
        print("benchmark/run.py: no localai_tfp_tpu/ beside benchmark/ — "
              "run it from the root of a checkout", file=sys.stderr)
        return 2
    plat = os.environ.get("JAX_PLATFORMS", "")
    if plat and "tpu" not in plat.lower().split(","):
        print(f"benchmark/run.py: JAX_PLATFORMS={plat!r} holds JAX off the "
              "TPU; the benchmark has no CPU mode", file=sys.stderr)
        return 3
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start)
    except HarnessFailure as e:
        CHILDREN.stop_all()
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
