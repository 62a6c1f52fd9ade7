"""Batched token sampling for the decode hot loop (pure JAX, jit-fused).

Capability counterpart of the reference's per-slot sampling
(ref: backend/cpp/llama/grpc-server.cpp — `llama_sampling_sample` inside
`update_slots` :2060, per-slot sampling params `llama_client_slot`
:188-265; surface: core/schema/prediction.go PredictionOptions).

TPU-first design: one compiled sampler handles the whole slot batch every
step. All per-request knobs are *arrays* indexed by slot, not Python
scalars — mixed temperature/top-k/top-p across slots never retrigger
compilation, and the sampler fuses into the decode step dispatch.

Penalty state (token counts over a sliding window of the last ``repeat_last_n``
tokens) is carried as a dense [n_slots, vocab] count matrix updated
incrementally on-device: O(1) per step instead of re-scanning history.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


@dataclass
class SamplingState:
    """Per-slot sampling parameters + PRNG + penalty state, all device arrays.

    Shapes: everything leading dim ``n_slots``. A slot's row is rewritten
    (host->device of a few scalars) when a request is admitted.
    """

    rng: jax.Array  # [S, 2] uint32 per-slot PRNG keys
    temperature: jax.Array  # [S] f32; <=0 => greedy
    top_k: jax.Array  # [S] i32; 0 => disabled
    top_p: jax.Array  # [S] f32; >=1 => disabled
    min_p: jax.Array  # [S] f32; 0 => disabled
    repeat_penalty: jax.Array  # [S] f32; 0 or 1 => disabled
    freq_penalty: jax.Array  # [S] f32
    presence_penalty: jax.Array  # [S] f32
    token_counts: jax.Array  # [S, V] i32 counts within penalty window
    history: jax.Array  # [S, W] i32 ring buffer of recent tokens (-1 empty)
    history_pos: jax.Array  # [S] i32 ring write cursor
    repeat_last_n: jax.Array  # [S] i32 effective window size (<= W)
    typical_p: jax.Array  # [S] f32; >=1 => disabled (locally typical)
    mirostat: jax.Array  # [S] i32; 0 off, 1 v1, 2 v2
    mirostat_tau: jax.Array  # [S] f32 target surprise (bits)
    mirostat_eta: jax.Array  # [S] f32 learning rate
    mirostat_mu: jax.Array  # [S] f32 adaptive cutoff (2*tau at reset)

    @classmethod
    def create(cls, n_slots: int, vocab_size: int, window: int = 256,
               seed: int = 0) -> "SamplingState":
        keys = jax.random.split(jax.random.PRNGKey(seed), n_slots)
        return cls(
            rng=keys,
            temperature=jnp.zeros((n_slots,), jnp.float32),
            top_k=jnp.zeros((n_slots,), jnp.int32),
            top_p=jnp.ones((n_slots,), jnp.float32),
            min_p=jnp.zeros((n_slots,), jnp.float32),
            repeat_penalty=jnp.zeros((n_slots,), jnp.float32),
            freq_penalty=jnp.zeros((n_slots,), jnp.float32),
            presence_penalty=jnp.zeros((n_slots,), jnp.float32),
            token_counts=jnp.zeros((n_slots, vocab_size), jnp.int32),
            history=jnp.full((n_slots, window), -1, jnp.int32),
            history_pos=jnp.zeros((n_slots,), jnp.int32),
            repeat_last_n=jnp.full((n_slots,), min(64, window), jnp.int32),
            typical_p=jnp.ones((n_slots,), jnp.float32),
            mirostat=jnp.zeros((n_slots,), jnp.int32),
            mirostat_tau=jnp.full((n_slots,), 5.0, jnp.float32),
            mirostat_eta=jnp.full((n_slots,), 0.1, jnp.float32),
            mirostat_mu=jnp.full((n_slots,), 10.0, jnp.float32),
        )

    @property
    def window(self) -> int:
        return self.history.shape[1]

    def reset_slot(self, slot: int, *, temperature: float = 0.0,
                   top_k: int = 0, top_p: float = 1.0, min_p: float = 0.0,
                   repeat_penalty: float = 0.0, freq_penalty: float = 0.0,
                   presence_penalty: float = 0.0, repeat_last_n: int = 64,
                   seed: Optional[int] = None, typical_p: float = 1.0,
                   mirostat: int = 0, mirostat_tau: float = 5.0,
                   mirostat_eta: float = 0.1) -> "SamplingState":
        """Host-side: configure one slot for a new request."""
        s = slot
        st = self
        rng = st.rng
        if seed is not None:
            rng = rng.at[s].set(jax.random.PRNGKey(seed))
        return SamplingState(
            rng=rng,
            temperature=st.temperature.at[s].set(temperature),
            top_k=st.top_k.at[s].set(top_k),
            top_p=st.top_p.at[s].set(top_p),
            min_p=st.min_p.at[s].set(min_p),
            repeat_penalty=st.repeat_penalty.at[s].set(repeat_penalty),
            freq_penalty=st.freq_penalty.at[s].set(freq_penalty),
            presence_penalty=st.presence_penalty.at[s].set(presence_penalty),
            token_counts=st.token_counts.at[s].set(0),
            history=st.history.at[s].set(-1),
            history_pos=st.history_pos.at[s].set(0),
            repeat_last_n=st.repeat_last_n.at[s].set(
                min(repeat_last_n if repeat_last_n > 0 else 64, st.window)
            ),
            typical_p=st.typical_p.at[s].set(typical_p),
            mirostat=st.mirostat.at[s].set(mirostat),
            mirostat_tau=st.mirostat_tau.at[s].set(mirostat_tau),
            mirostat_eta=st.mirostat_eta.at[s].set(mirostat_eta),
            # mirostat's adaptive cutoff starts at 2*tau (the paper's and
            # llama.cpp's initialisation)
            mirostat_mu=st.mirostat_mu.at[s].set(2.0 * mirostat_tau),
        )


jax.tree_util.register_pytree_node(
    SamplingState,
    lambda s: (
        tuple(getattr(s, f.name) for f in dataclasses.fields(s)),
        None,
    ),
    lambda _, ch: SamplingState(*ch),
)


@partial(jax.jit, donate_argnums=(0,))
def reset_slots(
    state: SamplingState,
    slot_ids: jax.Array,  # [K] i32; may repeat (padding rows repeat row 0)
    temperature: jax.Array,  # [K] f32
    top_k: jax.Array,  # [K] i32
    top_p: jax.Array,  # [K] f32
    min_p: jax.Array,  # [K] f32
    repeat_penalty: jax.Array,  # [K] f32
    freq_penalty: jax.Array,  # [K] f32
    presence_penalty: jax.Array,  # [K] f32
    repeat_last_n: jax.Array,  # [K] i32 (already clamped host-side)
    seeds: jax.Array,  # [K] i32
    has_seed: jax.Array,  # [K] bool
    typical_p: jax.Array,  # [K] f32
    mirostat: jax.Array,  # [K] i32
    mirostat_tau: jax.Array,  # [K] f32
    mirostat_eta: jax.Array,  # [K] f32
) -> SamplingState:
    """Configure a BATCH of slots in one dispatch (it rides the
    mixed dispatch — engine._reset_columns).

    ``reset_slot`` costs ~12 unbatched buffer copies per slot (including
    the [S, V] count matrix), which dominated admission waves. Padding rows point at the OUT-OF-BOUNDS
    slot id n_slots: JAX drops their scatter updates (and clamps their
    gathers), so they never touch live sampler state. Do NOT pad with a
    live slot id — a duplicate index would clobber that slot."""
    keys = jax.vmap(jax.random.PRNGKey)(seeds)  # [K, 2]
    rng_rows = jnp.where(has_seed[:, None], keys, state.rng[slot_ids])
    return SamplingState(
        rng=state.rng.at[slot_ids].set(rng_rows),
        temperature=state.temperature.at[slot_ids].set(temperature),
        top_k=state.top_k.at[slot_ids].set(top_k),
        top_p=state.top_p.at[slot_ids].set(top_p),
        min_p=state.min_p.at[slot_ids].set(min_p),
        repeat_penalty=state.repeat_penalty.at[slot_ids].set(repeat_penalty),
        freq_penalty=state.freq_penalty.at[slot_ids].set(freq_penalty),
        presence_penalty=state.presence_penalty.at[slot_ids].set(
            presence_penalty),
        token_counts=state.token_counts.at[slot_ids].set(0),
        history=state.history.at[slot_ids].set(-1),
        history_pos=state.history_pos.at[slot_ids].set(0),
        repeat_last_n=state.repeat_last_n.at[slot_ids].set(repeat_last_n),
        typical_p=state.typical_p.at[slot_ids].set(typical_p),
        mirostat=state.mirostat.at[slot_ids].set(mirostat),
        mirostat_tau=state.mirostat_tau.at[slot_ids].set(mirostat_tau),
        mirostat_eta=state.mirostat_eta.at[slot_ids].set(mirostat_eta),
        mirostat_mu=state.mirostat_mu.at[slot_ids].set(2.0 * mirostat_tau),
    )


def observe_tokens(state: SamplingState, slot_ids: jax.Array,
                   tokens: jax.Array, valid: jax.Array) -> SamplingState:
    """Record tokens (prompt or sampled) into the penalty window.

    slot_ids/tokens/valid: [B]. Evicts the token falling out of each slot's
    ring window from ``token_counts`` so counts always reflect exactly the
    last ``repeat_last_n`` tokens (ref: llama.cpp penalize window
    `repeat_last_n`, grpc-server.cpp slot sampling params).
    """
    W = state.window
    pos = state.history_pos[slot_ids]  # [B]
    n = state.repeat_last_n[slot_ids]  # [B] per-slot window size
    # token leaving the last-n window (written n steps ago)
    old = jnp.where(
        pos >= n, state.history[slot_ids, (pos - n) % W], -1
    )
    counts = state.token_counts
    # decrement evicted (only if a real token was there and op is valid)
    dec = valid & (old >= 0)
    counts = counts.at[slot_ids, jnp.where(old >= 0, old, 0)].add(
        -dec.astype(jnp.int32)
    )
    inc = valid & (tokens >= 0)
    counts = counts.at[slot_ids, jnp.where(tokens >= 0, tokens, 0)].add(
        inc.astype(jnp.int32)
    )
    hist = state.history.at[slot_ids, pos % W].set(
        jnp.where(valid, tokens, state.history[slot_ids, pos % W])
    )
    newpos = jnp.where(valid, pos + 1, pos)
    return dataclasses.replace(
        state, token_counts=counts, history=hist,
        history_pos=state.history_pos.at[slot_ids].set(newpos),
    )


@jax.jit
def observe_sequence(state: SamplingState, slot_id: jax.Array,
                     tokens: jax.Array, length: jax.Array) -> SamplingState:
    """Sequentially record ``tokens[:length]`` (padded [T]) into one slot's
    penalty window — used to seed the window with the prompt tail. A scan,
    because successive tokens in one slot must update the ring in order."""

    def body(st, tok_i):
        tok, i = tok_i
        return (
            observe_tokens(st, slot_id[None], tok[None], (i < length)[None]),
            None,
        )

    state, _ = lax.scan(
        body, state, (tokens, jnp.arange(tokens.shape[0], dtype=jnp.int32))
    )
    return state


def seed_windows(state: SamplingState, slot_ids: jax.Array,
                 tails: jax.Array, tail_lens: jax.Array) -> SamplingState:
    """Seed freshly-reset slots' penalty windows from their prompt tails
    in CLOSED FORM — equivalent to scanning ``observe_tokens`` over the
    tail, but O(1) depth instead of W sequential steps (the scan
    dominated the fused prefill dispatch: W=256 sequential scatter
    steps). slot_ids [B]; tails [B, W] (prompt[-W:], left-aligned);
    tail_lens [B]. Requires the target slots to be in the reset state
    (counts 0, history -1, pos 0) — exactly how the engine calls it."""
    W = state.window
    V = state.token_counts.shape[-1]
    T = tail_lens[:, None]  # [B, 1]
    n = jnp.minimum(state.repeat_last_n[slot_ids][:, None], T)  # [B, 1]
    j = jnp.arange(tails.shape[1], dtype=jnp.int32)[None, :]  # [1, W]
    in_window = (j >= T - n) & (j < T)  # counted positions
    safe = jnp.where((j < T) & (tails >= 0), tails, V)  # V = drop row

    def count_row(tokens_row, mask_row):
        return jnp.zeros(V + 1, jnp.int32).at[tokens_row].add(
            mask_row.astype(jnp.int32))[:V]

    counts_rows = jax.vmap(count_row)(safe, in_window)  # [B, V]
    hist_rows = jnp.where(j < T, tails, -1)  # [B, W] ring images
    if tails.shape[1] < W:
        hist_rows = jnp.pad(hist_rows, ((0, 0), (0, W - tails.shape[1])),
                            constant_values=-1)
    return dataclasses.replace(
        state,
        token_counts=state.token_counts.at[slot_ids].set(counts_rows),
        history=state.history.at[slot_ids].set(hist_rows),
        history_pos=state.history_pos.at[slot_ids].set(tail_lens),
    )


def _apply_penalties(logits: jax.Array, counts: jax.Array,
                     repeat_penalty: jax.Array, freq_penalty: jax.Array,
                     presence_penalty: jax.Array) -> jax.Array:
    """llama.cpp-convention penalties (ref: common/sampling in llama.cpp used
    by grpc-server.cpp): repeat divides positive logits / multiplies
    negative; frequency/presence are OpenAI-style subtractive."""
    present = counts > 0
    rp = jnp.where(repeat_penalty[:, None] > 0, repeat_penalty[:, None], 1.0)
    penalized = jnp.where(logits > 0, logits / rp, logits * rp)
    logits = jnp.where(present, penalized, logits)
    logits = logits - counts.astype(jnp.float32) * freq_penalty[:, None]
    logits = logits - present.astype(jnp.float32) * presence_penalty[:, None]
    return logits


# Static candidate-set size for stochastic sampling. llama.cpp chains
# samplers top_k (default 40) -> top_p -> min_p, so computing the
# top-p/min-p cutoffs within the top-CAND candidates reproduces the
# reference semantics whenever top_k <= CAND (llama.cpp default 40); with
# top_k disabled it truncates the distribution's tail beyond the top-128,
# which carries negligible mass at sane temperatures. A full-vocab sort
# here would dominate the whole decode step on TPU (3 sorts x V=128k).
CAND = 128


def _topk_scaled(state: SamplingState, slot_ids: jax.Array,
                 logits: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Shared candidate prologue for ``sample`` and
    ``filtered_candidates``: top-CAND truncation + temperature scaling.
    ONE implementation so the decode sampler and the speculative
    rejection distribution cannot drift apart."""
    logits = logits.astype(jnp.float32)
    K = min(CAND, logits.shape[-1])
    if logits.shape[-1] >= 16384:
        # TPU-native approximate top-k: the exact lax.top_k lowers to a
        # full [B, V] sort — measured ~12.6 ms/step of the 8B decode's
        # 31 ms at V=128k (tools/microbench_step.py r5). approx_max_k
        # reduces per-window maxima first: the TRUE argmax is always in
        # some window, so rank-1 (greedy) stays EXACT; deeper ranks can
        # drop a candidate that shares a window with a larger one —
        # bounded by recall_target and far below the mass the K=CAND
        # truncation already discards. Small vocabs (and CPU, where
        # approx falls back to exact) keep the exact sort.
        vals, idx = lax.approx_max_k(logits, K, recall_target=0.95)
    else:
        vals, idx = lax.top_k(logits, K)  # [B, K] desc
    temp = state.temperature[slot_ids]
    scaled = vals / jnp.maximum(temp, 1e-6)[:, None]
    return scaled, idx


def _chain_probs(state: SamplingState, slot_ids: jax.Array,
                 scaled: jax.Array) -> jax.Array:
    """top_k -> typical_p -> top_p -> min_p over temp-scaled candidate
    logits ``scaled`` [B, K] (desc order). Returns probs [B, K]."""
    K = scaled.shape[-1]
    rank = jnp.arange(K, dtype=jnp.int32)[None, :]
    k_eff = jnp.where(state.top_k[slot_ids] <= 0, K,
                      state.top_k[slot_ids])[:, None]
    scaled = jnp.where(rank < k_eff, scaled, NEG_INF)
    # locally typical filter, between top_k and top_p (llama.cpp chain
    # order top_k -> typ_p -> top_p -> min_p; llama_sampler_typical):
    # keep the smallest candidate set, ordered by |surprise - entropy|,
    # whose cumulative probability reaches typical_p
    typ = state.typical_p[slot_ids][:, None]  # [B, 1]
    probs = jax.nn.softmax(scaled, axis=-1)
    logp = jnp.where(probs > 0, jnp.log(jnp.maximum(probs, 1e-30)), NEG_INF)
    entropy = -jnp.sum(jnp.where(probs > 0, probs * logp, 0.0), axis=-1,
                       keepdims=True)  # [B, 1]
    dev = jnp.where(probs > 0, jnp.abs(-logp - entropy), jnp.inf)
    order = jnp.argsort(dev, axis=-1)  # ascending deviation
    p_sorted = jnp.take_along_axis(probs, order, axis=-1)
    cum = jnp.cumsum(p_sorted, axis=-1)
    keep_sorted = (cum - p_sorted) < typ  # first crossing kept
    keep = jnp.zeros_like(keep_sorted).at[
        jnp.arange(order.shape[0])[:, None], order].set(keep_sorted)
    scaled = jnp.where(keep | (typ >= 1.0), scaled, NEG_INF)
    probs = jax.nn.softmax(scaled, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < state.top_p[slot_ids][:, None]
    scaled = jnp.where(keep, scaled, NEG_INF)
    probs = jax.nn.softmax(scaled, axis=-1)
    keep = probs >= probs[:, :1] * state.min_p[slot_ids][:, None]
    scaled = jnp.where(keep, scaled, NEG_INF)
    return jax.nn.softmax(scaled, axis=-1)


_LOG2E = 1.4426950408889634  # 1/ln(2): nats -> bits


def _mirostat_probs(state: SamplingState, slot_ids: jax.Array,
                    scaled: jax.Array, vocab: int) -> jax.Array:
    """Mirostat v1/v2 candidate distribution (ref: llama.cpp
    llama_sampler_mirostat{,_v2}, the reference's default sampler mode —
    grpc-server.cpp:708-710, docs/content/docs/faq.md:19-21). Truncation
    only — the adaptive mu update happens in ``sample`` after the draw.

    v2: drop candidates whose surprise (-log2 p) exceeds mu.
    v1: estimate the Zipf exponent s_hat from the top candidates, derive
        k from (s_hat, mu, vocab), truncate to top-k."""
    probs = jax.nn.softmax(scaled, axis=-1)  # temp-applied, full cand set
    K = scaled.shape[-1]
    rank = jnp.arange(K, dtype=jnp.int32)[None, :]
    mu = state.mirostat_mu[slot_ids][:, None]  # [B, 1]
    surprise = -jnp.log2(jnp.maximum(probs, 1e-30))
    keep_v2 = surprise <= mu
    # v1: linear-regression estimate of the Zipf exponent over the top m
    # candidates: s_hat = sum(t_i * b_i) / sum(t_i^2), with
    # t_i = log((i+2)/(i+1)), b_i = log(p_i / p_{i+1})
    m = min(100, K)
    i = jnp.arange(m - 1, dtype=jnp.float32)
    t = jnp.log((i + 2.0) / (i + 1.0))[None, :]  # [1, m-1]
    p_top = jnp.maximum(probs[:, :m], 1e-30)
    b = jnp.log(p_top[:, :-1] / p_top[:, 1:])  # [B, m-1]
    s_hat = jnp.sum(t * b, axis=-1, keepdims=True) / jnp.sum(t * t)
    eps = s_hat - 1.0
    # k = ((eps * 2^mu) / (1 - N^(-eps)))^(1/s_hat)  (mirostat paper eq. 6)
    n_f = jnp.float32(vocab)
    k1 = jnp.power(
        (eps * jnp.power(2.0, mu))
        / jnp.maximum(1.0 - jnp.power(n_f, -eps), 1e-6),
        1.0 / jnp.maximum(s_hat, 1e-6),
    )
    keep_v1 = rank < jnp.maximum(jnp.round(k1), 1.0).astype(jnp.int32)
    is_v1 = (state.mirostat[slot_ids] == 1)[:, None]
    keep = jnp.where(is_v1, keep_v1, keep_v2)
    keep = keep | (rank == 0)  # always at least the argmax
    return jax.nn.softmax(jnp.where(keep, scaled, NEG_INF), axis=-1)


def filtered_candidates(
    state: SamplingState,
    slot_ids: jax.Array,  # [B] i32
    logits: jax.Array,  # [B, V] f32
) -> tuple[jax.Array, jax.Array]:
    """Per-row candidate DISTRIBUTION after the temperature/top-k/
    typical-p/top-p/min-p chain — the same llama.cpp sampler pipeline as
    ``sample`` minus penalties and mirostat (callers enforce
    penalty-free, mirostat-free eligibility). Returns (probs [B, CAND],
    vocab idx [B, CAND]); temp<=0 rows are an exact one-hot on the
    argmax. Used by speculative REJECTION sampling, which needs both
    models' filtered distributions, not just a draw."""
    scaled, idx = _topk_scaled(state, slot_ids, logits)
    temp = state.temperature[slot_ids]
    probs = _chain_probs(state, slot_ids, scaled)
    rank = jnp.arange(scaled.shape[-1], dtype=jnp.int32)[None, :]
    greedy = (rank == 0).astype(jnp.float32)  # candidates sorted desc
    return jnp.where((temp <= 0.0)[:, None], greedy, probs), idx


def sample(
    state: SamplingState,
    slot_ids: jax.Array,  # [B] i32 — which slot each logits row belongs to
    logits: jax.Array,  # [B, V] f32 — last-position logits
    mask: Optional[jax.Array] = None,  # [B, V] bool — grammar/logit-bias mask
) -> tuple[jax.Array, SamplingState]:
    """Sample one token per row; returns ([B] i32 tokens, updated state).

    Greedy when temperature<=0 (reference behavior: temp==0 => argmax).
    The token is recorded into the penalty window.
    """
    logits = logits.astype(jnp.float32)
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)

    counts = state.token_counts[slot_ids]
    logits = _apply_penalties(
        logits, counts,
        state.repeat_penalty[slot_ids],
        state.freq_penalty[slot_ids],
        state.presence_penalty[slot_ids],
    )

    # the shared filter chain: ONE implementation feeds both this sampler
    # and speculative rejection sampling, so their distributions can never
    # drift apart. Mirostat rows (llama.cpp semantics) bypass the chain:
    # temperature + adaptive-surprise truncation only.
    V = logits.shape[-1]
    scaled, idx = _topk_scaled(state, slot_ids, logits)
    temp = state.temperature[slot_ids]
    rank = jnp.arange(scaled.shape[-1], dtype=jnp.int32)[None, :]
    greedy_row = (rank == 0).astype(jnp.float32)
    chain = _chain_probs(state, slot_ids, scaled)
    miro = state.mirostat[slot_ids]
    miro_probs = _mirostat_probs(state, slot_ids, scaled, V)
    probs = jnp.where((miro > 0)[:, None], miro_probs, chain)
    probs = jnp.where((temp <= 0.0)[:, None], greedy_row, probs)
    greedy_tok = idx[:, 0].astype(jnp.int32)  # candidates sorted desc

    keys = state.rng[slot_ids]
    split = jax.vmap(jax.random.split)(keys)  # [B, 2, 2]
    new_keys, sample_keys = split[:, 0], split[:, 1]
    # gumbel-max over log probs == over filtered logits (per-row constant
    # shift preserves the argmax), so draws match the pre-refactor sampler
    logp = jnp.where(probs > 0, jnp.log(jnp.maximum(probs, 1e-30)), NEG_INF)
    gumbel = jax.vmap(
        lambda k, row: jax.random.gumbel(k, row.shape, jnp.float32)
    )(sample_keys, logp)
    j = jnp.argmax(logp + gumbel, axis=-1)
    sampled_tok = jnp.take_along_axis(idx, j[:, None], axis=-1)[:, 0].astype(
        jnp.int32
    )

    tok = jnp.where(temp <= 0.0, greedy_tok, sampled_tok)

    # mirostat mu update: observed surprise of the drawn token (bits,
    # from the truncated+renormalized distribution, as llama.cpp computes
    # it post-softmax), mu -= eta * (observed - tau)
    p_drawn = jnp.take_along_axis(probs, j[:, None], axis=-1)[:, 0]
    observed = -jnp.log2(jnp.maximum(p_drawn, 1e-30))
    mu = state.mirostat_mu[slot_ids]
    mu_new = mu - state.mirostat_eta[slot_ids] * (
        observed - state.mirostat_tau[slot_ids])
    mu_rows = jnp.where((miro > 0) & (temp > 0.0), mu_new, mu)

    state = dataclasses.replace(
        state,
        rng=state.rng.at[slot_ids].set(new_keys),
        mirostat_mu=state.mirostat_mu.at[slot_ids].set(mu_rows),
    )
    valid = jnp.ones(tok.shape, bool)
    state = observe_tokens(state, slot_ids, tok, valid)
    return tok, state
