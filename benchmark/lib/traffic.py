"""One general traffic generator, driven by a mix file of parameters.

A mix (``benchmark/traffic/<name>.json``) plus a cell's own overrides
(``benchmark/cells/<cell>.json``, optional: the rate found by that cell's
sweep) gives a *schedule*: a list of requests, each with the time it is
due (seconds from the start of the measured window; negative = pre-roll),
a prompt length, an output length and what it shares. Unknown fields are
an error; an absent field means "none".

Steadiness: every ``--seed`` gets the SAME multiset of prompt lengths,
output lengths and arrival gaps — the quantiles of the stated
distributions, (i + 0.5) / n, i.e. the distribution stratified into n
equal shares with one value from each — in an order drawn from the
seed, and other prompt text. So two seeds differ by order and text,
never by the amount of work, and two seeds' orders are independent
shuffles (any whole number is a seed). What a statistic of few requests
still owes to the order (which long prompts land together) is spread a
run's bound has to cover, not something the generator hides.
A ``gamma`` gap has no closed-form quantile: its multiset is drawn once
from a generator seeded by the mix's ``shape_seed``.

Fields of a mix:
  loop              "open" (arrivals on a schedule) | "closed" (N clients,
                    each sends its next request when its reply ends)
  rate_rps          open loop: mean arrivals per second
  clients           closed loop: number of clients
  arrivals          {"dist": "poisson"} | {"dist": "gamma", "cv": c}
  prompt_tokens     a distribution (below)
  output_tokens     a distribution
  shared_prefix_tokens  tokens of one prompt prefix common to all requests
  sessions          {"turns": dist, "grow_tokens": dist, "think_s": dist}:
                    a scheduled request opens a session; turn t+1 is due
                    think_s after turn t's reply ended and its prompt is
                    turn t's prompt + grow_tokens of new text
  burst             {"every_s": s, "count": n, "prompt_tokens": dist,
                    "output_tokens": dist}: n extra requests at once
  preroll_s         seconds of the same traffic before the window opens
  preroll_cycles    the same, in window lengths. The pre-roll is the END
                    of the previous period of the window's own cycle: a
                    request due at d has a twin due at d - seconds (other
                    text). With one whole cycle, every shape the window
                    will use has been used — and so traced, compiled or
                    loaded — before it opens, and the queue is in steady
                    state at 0. It counts as set-up.
  warm_episode_s    before the measured episode (pre-roll + window), the
                    same traffic from its own start for this long, same
                    seed, other text, then drained: the server loads each
                    program variant on first use inside the serving loop,
                    so whatever the traffic reaches by this age has been
                    reached once before the window. It counts as set-up.
  ramp_s            closed loop: client starts spread over this long
  drain_s           grace for requests still streaming at window end
  endpoint, request the route and the fixed body fields
  shape_seed        seed of the one fixed multiset that needs random draws
                    (gamma gaps)
Distributions: {"dist": "fixed", "value": v} | {"dist": "uniform",
"min", "max"} | {"dist": "lognormal", "median", "sigma", "min", "max"}.
"""

from __future__ import annotations

import json
import math
import random
from statistics import NormalDist

MIX_FIELDS = {
    "loop", "rate_rps", "clients", "arrivals", "prompt_tokens",
    "output_tokens", "shared_prefix_tokens", "sessions", "burst",
    "preroll_s", "preroll_cycles", "warm_episode_s", "ramp_s", "drain_s",
    "endpoint",
    "request", "shape_seed", "notes",
}
_REQUIRED = {"loop", "prompt_tokens", "output_tokens", "endpoint"}


class MixError(ValueError):
    pass


def load_mix(path: str, overrides: "dict | None" = None) -> dict:
    with open(path) as f:
        mix = json.load(f)
    mix.update({k: v for k, v in (overrides or {}).items()
                if k in MIX_FIELDS})
    check_mix(mix)
    return mix


def check_mix(mix: dict) -> None:
    unknown = set(mix) - MIX_FIELDS
    if unknown:
        raise MixError(f"unknown traffic fields: {sorted(unknown)}")
    missing = _REQUIRED - set(mix)
    if missing:
        raise MixError(f"missing traffic fields: {sorted(missing)}")
    if float(mix.get("warm_episode_s") or 0.0) < 0:
        raise MixError("warm_episode_s must be >= 0")
    if mix["loop"] == "open":
        if not mix.get("rate_rps") or mix["rate_rps"] <= 0:
            raise MixError("an open loop needs rate_rps > 0 (in the mix "
                           "or the cell's own file)")
    elif mix["loop"] == "closed":
        if not mix.get("clients") or mix["clients"] < 1:
            raise MixError("a closed loop needs clients >= 1")
        if mix.get("sessions") or mix.get("burst"):
            raise MixError("sessions/burst are open-loop fields")
    else:
        raise MixError(f"loop must be open or closed, not {mix['loop']!r}")


def quantiles(dist: dict, n: int, shape_seed: int = 0) -> list:
    """The n values of ``dist`` at quantiles (i + 0.5) / n, ascending."""
    us = [(i + 0.5) / n for i in range(n)]
    kind = dist["dist"]
    if kind == "fixed":
        vals = [float(dist["value"])] * n
    elif kind == "uniform":
        lo, hi = float(dist["min"]), float(dist["max"])
        vals = [lo + (hi - lo) * u for u in us]
    elif kind == "lognormal":
        nd = NormalDist()
        mu, sg = math.log(dist["median"]), float(dist["sigma"])
        vals = [math.exp(mu + sg * nd.inv_cdf(u)) for u in us]
    elif kind == "exponential":
        vals = [-math.log(1.0 - u) * float(dist.get("mean", 1.0))
                for u in us]
    elif kind == "gamma":  # mean 1, coefficient of variation cv
        cv = float(dist["cv"])
        k = 1.0 / (cv * cv)
        rng = random.Random(shape_seed)
        vals = sorted(rng.gammavariate(k, 1.0 / k) for _ in range(n))
    else:
        raise MixError(f"unknown distribution {kind!r}")
    if "min" in dist and kind != "uniform":
        vals = [max(float(dist["min"]), v) for v in vals]
    if "max" in dist and kind != "uniform":
        vals = [min(float(dist["max"]), v) for v in vals]
    return vals


class _Order:
    """The seed's order of a mix's fixed multisets: every list of values
    is shuffled by one generator seeded from ``--seed``, so two seeds
    give independent orders of the same work."""

    def __init__(self, seed: int) -> None:
        self.rng = random.Random(int(seed))

    def place(self, vals: list) -> list:
        vals = list(vals)
        self.rng.shuffle(vals)
        return vals


def _ints(dist: dict, n: int, order: _Order, shape_seed: int) -> list:
    return order.place(
        [max(1, int(round(v))) for v in quantiles(dist, n, shape_seed)])


def _gaps(mix: dict, n: int, span: float, order: _Order) -> list:
    """n arrival gaps summing to ``span``: the fixed multiset of the
    arrival process, in the seed's order."""
    arr = mix.get("arrivals") or {"dist": "poisson"}
    dist = ({"dist": "exponential"} if arr["dist"] == "poisson"
            else dict(arr))
    g = order.place(quantiles(dist, n, int(mix.get("shape_seed", 0))))
    scale = span / sum(g)
    return [x * scale for x in g]


def _segment(mix: dict, t0: float, span: float, order: _Order,
             shape_seed: int) -> list:
    """Requests due in [t0, t0 + span): the open loop's arrivals."""
    n = int(round(mix["rate_rps"] * span))
    if n <= 0:
        return []
    gaps = _gaps(mix, n, span, order)
    p = _ints(mix["prompt_tokens"], n, order, shape_seed)
    o = _ints(mix["output_tokens"], n, order, shape_seed)
    out, t = [], t0 - gaps[0] / 2.0
    for i in range(n):
        t += gaps[i]
        out.append({"due": t, "prompt_tokens": p[i], "output_tokens": o[i]})
    return out


def preroll(mix: dict, seconds: float) -> float:
    """Seconds of traffic before the window opens."""
    return float(mix.get("preroll_s", 0.0)) + float(
        mix.get("preroll_cycles", 0.0)) * float(seconds)


def schedule(mix: dict, seed: int, seconds: float) -> dict:
    """-> {"loop", "requests": [...], "clients"}. Open loop: requests
    sorted by ``due``. Closed loop: ``requests`` is the queue the
    clients draw from, ``starts`` the time each client begins."""
    check_mix(mix)
    shape_seed = int(mix.get("shape_seed", 0))
    order = _Order(seed)
    pre = preroll(mix, seconds)
    shared = int(mix.get("shared_prefix_tokens") or 0)
    if mix["loop"] == "open":
        reqs = _segment(mix, 0.0, float(seconds), order, shape_seed)
        burst = mix.get("burst")
        if burst:
            t = float(burst["every_s"]) / 2.0
            while t < seconds:
                n = int(burst["count"])
                p = _ints(burst["prompt_tokens"], n, order, shape_seed)
                o = _ints(burst["output_tokens"], n, order, shape_seed)
                reqs += [{"due": t, "prompt_tokens": p[i],
                          "output_tokens": o[i], "burst": True}
                         for i in range(n)]
                t += float(burst["every_s"])
        twins, j = [], 1
        while (j - 1) * seconds < pre:
            twins += [dict(r, due=r["due"] - j * seconds) for r in reqs
                      if r["due"] - j * seconds >= -pre]
            j += 1
        reqs = sorted(twins + reqs, key=lambda r: r["due"])
        sess = mix.get("sessions")
        if sess:
            n = len(reqs)
            turns = _ints(sess["turns"], n, order, shape_seed)
            for r, k in zip(reqs, turns):
                r["turns"] = k
                r["grow_tokens"] = _ints(sess["grow_tokens"], k, order,
                                         shape_seed)
                r["think_s"] = order.place([float(v) for v in quantiles(
                    sess["think_s"], k, shape_seed)])
        out = {"loop": "open", "requests": reqs}
    else:
        n_cl = int(mix["clients"])
        # cycles of one fixed multiset of 2N sizes; 50 requests a second
        # is more than any window can finish (the queue wraps if not)
        cycle = 2 * n_cl
        n_cycles = int(math.ceil((float(seconds) + pre) * 50.0 / cycle)) + 1
        reqs = []
        for _ in range(n_cycles):
            p = _ints(mix["prompt_tokens"], cycle, order, shape_seed)
            o = _ints(mix["output_tokens"], cycle, order, shape_seed)
            reqs += [{"due": None, "prompt_tokens": p[i],
                      "output_tokens": o[i]} for i in range(cycle)]
        ramp = float(mix.get("ramp_s", 0.0))
        starts = [-pre + ramp * i / n_cl for i in range(n_cl)]
        out = {"loop": "closed", "requests": reqs, "clients": n_cl,
               "starts": starts}
    for i, r in enumerate(out["requests"]):
        r["id"] = i
        r["shared_prefix_tokens"] = shared
    return out


class PromptMaker:
    """Prompt text of a wanted token length from the configuration's own
    tokenizer: slices of one seeded id stream, decoded. Every prompt
    starts with text unique to (seed, request), so no two share a
    prefix unless the mix asks for one."""

    _WORDS = ("time people way water words number part sound work place "
              "year back thing name sentence line right mean old great "
              "cause system follow change light house picture animal "
              "point mother world near build self earth father head "
              "stand page country found answer school grow study learn "
              "plant cover food sun four between state keep eye never "
              "last let thought city tree cross farm hard start might "
              "story saw far sea draw left late run press close night "
              "real life few north open seem together next white "
              "children begin got walk example ease paper group always "
              "music those both mark often letter until mile river car "
              "feet care second book carry took science eat room friend "
              "began idea fish mountain stop once base hear horse cut "
              "sure watch color face wood main enough plain girl usual "
              "young ready above ever red list though feel talk bird "
              "soon body dog family direct pose leave song measure door "
              "product black short numeral class wind question happen "
              "complete ship area half rock order fire south problem "
              "piece told knew pass since top whole king space heard "
              "best hour better true during hundred five remember step "
              "early hold west ground interest reach fast verb sing "
              "listen six table travel less morning ten simple several "
              "vowel toward war lay against pattern slow center love "
              "person money serve appear road map rain rule govern pull "
              "cold notice voice unit power town fine certain fly fall "
              "lead cry dark machine note wait plan figure star box "
              "noun field rest correct able pound done beauty drive "
              "stood contain front teach week final gave green oh quick "
              "develop ocean warm free minute strong special mind "
              "behind clear tail produce fact street inch multiply "
              "nothing course stay wheel full force blue object decide "
              "surface deep moon island foot busy test record boat "
              "common gold possible plane stead dry wonder laugh "
              "thousand ago ran check game shape equate hot miss "
              "brought heat snow tire bring yes distant fill east paint "
              "language among").split()

    def __init__(self, tokenizer_json: str, seed: int,
                 stream_tokens: int = 60000) -> None:
        from tokenizers import Tokenizer

        self._tk = Tokenizer.from_file(tokenizer_json)
        self._seed = int(seed)
        rng = random.Random(self._seed * 7919 + 13)
        words: list = []
        ids: list = []
        while len(ids) < stream_tokens:
            words = [rng.choice(self._WORDS) for _ in range(4000)]
            ids += self._tk.encode(" " + " ".join(words),
                                   add_special_tokens=False).ids
        self._ids = ids
        self._rng = random.Random(self._seed * 104729 + 7)
        self._shared: dict = {}

    def count(self, text: str) -> int:
        return len(self._tk.encode(text, add_special_tokens=False).ids)

    def _slice(self, n: int) -> str:
        n = max(1, n)
        off = self._rng.randrange(0, len(self._ids) - n)
        return self._tk.decode(self._ids[off: off + n])

    def text(self, n_tokens: int, tag: str, shared: int = 0) -> str:
        """About ``n_tokens`` tokens of content: an optional shared
        prefix, then a unique tag, then filler."""
        head = ""
        if shared > 0:
            if shared not in self._shared:
                rng = random.Random(shared)
                off = rng.randrange(0, len(self._ids) - shared)
                self._shared[shared] = self._tk.decode(
                    self._ids[off: off + shared])
            head = self._shared[shared] + " "
        lead = f"[{tag}]"
        used = self.count(head + lead)
        return head + lead + self._slice(n_tokens - used)

    def grow(self, prompt: str, n_tokens: int, tag: str) -> str:
        """A session's next turn: the history plus new text."""
        return prompt + f" [{tag}]" + self._slice(n_tokens)
