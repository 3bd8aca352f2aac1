"""On-device batched sampling.

One jitted function samples the whole batch: greedy and
temperature/top-k/top-p/min-p paths are blended with `jnp.where` so a
mixed batch compiles once (no per-request Python branching —
XLA-friendly).

Full sampling surface (reference: lib/llm/src/protocols/common.rs
:263-309 SamplingOptions — the reference carries these into its vLLM
engines; here they execute on device):

- temperature / top_k / top_p / min_p / seed
- logit_bias: sparse per-slot (token id, bias) pairs scatter-added into
  the logits (OpenAI semantics) — base path, always compiled.
- frequency/presence/repetition penalties: need per-slot token-count
  state, so they ride a SEPARATELY-COMPILED step variant whose
  SamplingBatch carries sparse count tables ([B, N] ids + counts,
  bucketed). Inside a fused K-step decode window the counts are
  scattered into a dense [B, V] table once, carried through the scan,
  and updated on device after every sampled token — so window outputs
  match K single steps exactly.

Semantics follow vLLM (the reference's serving engine): frequency and
presence penalties count GENERATED tokens only; repetition penalty
applies to prompt + generated tokens (HF-style divide/multiply).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.protocols.common import SamplingOptions

NEG_INF = -1e30

# Sparse tables are pinned to ONE width each (not bucketed): a width
# change is a new jit signature, and a mid-serve compile of a
# 32-layer step is a TTFT stall of many seconds (ADVICE r3: the bucketed
# widths were reachable by any logit_bias request with >4 entries).
# BIAS_W covers OpenAI's 300-entry logit_bias cap outright; COUNT_W
# truncates penalty token-count tables at 4096 distinct ids (documented
# bound — beyond it the least-recently-sorted ids stop contributing).
BIAS_W = 512
COUNT_W = 4096
# top-logprob alternatives returned by the "top_lp" step variant
# (OpenAI caps top_logprobs at 20)
TOPLP_N = 20


@dataclass
class SamplingBatch:
    """Host-side per-slot sampling params, uploaded each step.

    ``arrays`` is a flat dict of numpy arrays (a jit-friendly pytree):

    base keys (always present):
      temperature [B] f32 (0 = greedy), top_k [B] i32 (0 = off),
      top_p [B] f32 (1 = off), min_p [B] f32 (0 = off), seeds [B] u32

    bias keys (only when a request in the batch carries logit_bias —
    presence selects the bias jit variant):
      bias_ids [B, BIAS_W] i32, bias_vals [B, BIAS_W] f32 (pad id 0/0)

    penalty keys (only when a request in the batch uses them — selects
    the penalty-variant compiled step):
      freq_pen [B] f32, pres_pen [B] f32, rep_pen [B] f32 (1 = off),
      gen_ids [B, NP] i32 + gen_counts [B, NP] f32 (generated tokens),
      prompt_ids [B, NR] i32 + prompt_counts [B, NR] f32 (presence=1)

    guided key (only when a request in the batch carries a guided
      constraint — selects the masked jit variant;
      docs/guided_decoding.md):
      allow_mask [B, V_pad] bool (unguided rows all-True); the spec
      verify step carries [B, S, V_pad] instead (per fed position)
    """

    arrays: dict[str, np.ndarray] = field(default_factory=dict)

    @property
    def temperature(self) -> np.ndarray:
        return self.arrays["temperature"]

    @property
    def seeds(self) -> np.ndarray:
        return self.arrays["seeds"]

    @property
    def has_penalties(self) -> bool:
        return "rep_pen" in self.arrays

    @property
    def has_bias(self) -> bool:
        return "bias_ids" in self.arrays

    @property
    def has_toplp(self) -> bool:
        return "top_lp_n" in self.arrays

    @property
    def has_guided(self) -> bool:
        return "allow_mask" in self.arrays

    @classmethod
    def from_options(
        cls,
        opts: list[SamplingOptions],
        step_seeds: list[int],
        gen_token_counts: Optional[list[dict[int, int]]] = None,
        prompt_token_ids: Optional[list[np.ndarray]] = None,
        top_lp: Optional[list[int]] = None,
    ) -> "SamplingBatch":
        """``gen_token_counts``/``prompt_token_ids`` (parallel to opts)
        supply the per-sequence token state the penalty path needs; they
        may be None when no option in the batch needs penalties.
        ``top_lp`` (per-slot requested alternative counts, any > 0)
        selects the top-logprobs step variant: sample() additionally
        returns the TOPLP_N most likely ids + logprobs per slot."""
        n = len(opts)
        a: dict[str, np.ndarray] = {
            "temperature": np.zeros((n,), np.float32),
            "top_k": np.zeros((n,), np.int32),
            "top_p": np.ones((n,), np.float32),
            "min_p": np.zeros((n,), np.float32),
            # host python list -> ndarray; no device array involved
            "seeds": np.asarray(step_seeds, np.uint32),  # dynalint: disable=transitive-host-sync-in-step-loop — host-list conversion
        }
        for i, o in enumerate(opts):
            if not o.use_greedy and o.temperature is not None:
                a["temperature"][i] = max(o.temperature, 1e-4)
            elif not o.use_greedy:
                a["temperature"][i] = 1.0
            if o.top_k:
                a["top_k"][i] = o.top_k
            if o.top_p is not None:
                a["top_p"][i] = o.top_p
            if o.min_p:
                a["min_p"][i] = o.min_p
        # sparse logit bias: PRESENCE-KEYED like the penalty tables —
        # batches with no bias (approximately all of them) ship nothing
        # and select the bias-free jit variant; bias batches carry one
        # fixed BIAS_W width (OpenAI caps logit_bias at 300 entries, so
        # nothing real ever truncates, and one width = one signature).
        if any(o.logit_bias for o in opts):
            a["bias_ids"] = np.zeros((n, BIAS_W), np.int32)
            a["bias_vals"] = np.zeros((n, BIAS_W), np.float32)
            for i, o in enumerate(opts):
                items = sorted((o.logit_bias or {}).items())[:BIAS_W]
                for j, (tok, v) in enumerate(items):
                    a["bias_ids"][i, j] = tok
                    a["bias_vals"][i, j] = v
        if any(o.needs_penalties for o in opts):
            a.update(
                cls._penalty_arrays(opts, gen_token_counts, prompt_token_ids)
            )
        if top_lp is not None and any(k > 0 for k in top_lp):
            # host python list -> ndarray; no device array involved
            a["top_lp_n"] = np.asarray(  # dynalint: disable=transitive-host-sync-in-step-loop — host-list conversion
                [min(max(k, 0), TOPLP_N) for k in top_lp], np.int32
            )
        return cls(a)

    @staticmethod
    def _penalty_arrays(
        opts: list[SamplingOptions],
        gen_token_counts: Optional[list[dict[int, int]]],
        prompt_token_ids: Optional[list[np.ndarray]],
    ) -> dict[str, np.ndarray]:
        n = len(opts)
        gen_token_counts = gen_token_counts or [{} for _ in opts]
        prompt_token_ids = prompt_token_ids or [
            np.zeros((0,), np.int32) for _ in opts
        ]
        a: dict[str, np.ndarray] = {
            "freq_pen": np.zeros((n,), np.float32),
            "pres_pen": np.zeros((n,), np.float32),
            "rep_pen": np.ones((n,), np.float32),
        }
        for i, o in enumerate(opts):
            if o.frequency_penalty:
                a["freq_pen"][i] = o.frequency_penalty
            if o.presence_penalty:
                a["pres_pen"][i] = o.presence_penalty
            if o.repetition_penalty:
                a["rep_pen"][i] = o.repetition_penalty
        # fixed COUNT_W width (one compiled penalty variant — see the
        # BIAS_W/COUNT_W note at the top of the module)
        a["gen_ids"] = np.zeros((n, COUNT_W), np.int32)
        a["gen_counts"] = np.zeros((n, COUNT_W), np.float32)
        a["prompt_ids"] = np.zeros((n, COUNT_W), np.int32)
        a["prompt_counts"] = np.zeros((n, COUNT_W), np.float32)
        for i, counts in enumerate(gen_token_counts):
            for j, (tok, c) in enumerate(sorted(counts.items())[:COUNT_W]):
                a["gen_ids"][i, j] = tok
                a["gen_counts"][i, j] = c
        for i, toks in enumerate(prompt_token_ids):
            # host python list -> ndarray; no device array involved
            t = np.asarray(toks, np.int32)[:COUNT_W]  # dynalint: disable=transitive-host-sync-in-step-loop — host-list conversion
            a["prompt_ids"][i, : len(t)] = t
            a["prompt_counts"][i, : len(t)] = 1.0
        return a


# ---------------------------------------------------------------------------
# Device side
# ---------------------------------------------------------------------------


def dense_gen_counts(s: dict, vocab: int) -> jax.Array:
    """Scatter the sparse generated-token table into a dense [B, V] f32
    (the fused-window carry: updated on device after each sampled
    token)."""
    B = s["gen_ids"].shape[0]
    rows = jnp.arange(B)[:, None]
    return (
        jnp.zeros((B, vocab), jnp.float32).at[rows, s["gen_ids"]].add(
            s["gen_counts"]
        )
    )


def dense_prompt_presence(s: dict, vocab: int) -> jax.Array:
    """Dense [B, V] f32 presence (>=1 where the token occurs in the
    prompt) — constant across a fused window."""
    B = s["prompt_ids"].shape[0]
    rows = jnp.arange(B)[:, None]
    return (
        jnp.zeros((B, vocab), jnp.float32).at[rows, s["prompt_ids"]].add(
            s["prompt_counts"]
        )
    )


def apply_penalties(
    logits: jax.Array,  # [B, V] f32
    s: dict,
    gen_dense: jax.Array,  # [B, V] f32 generated-token counts
    prompt_dense: jax.Array,  # [B, V] f32 prompt presence
) -> jax.Array:
    """HF-style repetition penalty over prompt+generated, then OpenAI
    frequency/presence over generated only (vLLM order)."""
    rp = s["rep_pen"][:, None]
    seen_any = (gen_dense + prompt_dense) > 0
    rep = jnp.where(logits > 0, logits / rp, logits * rp)
    logits = jnp.where(seen_any, rep, logits)
    logits = (
        logits
        - s["freq_pen"][:, None] * gen_dense
        - s["pres_pen"][:, None] * (gen_dense > 0)
    )
    return logits


def filter_keep_mask(
    vals: jax.Array,  # [..., KF] descending top-KF slice of scaled logits
    lse: jax.Array,  # [..., 1] full-vocab logsumexp of the scaled logits
    top_k: jax.Array,  # broadcastable against vals[..., :1]
    top_p: jax.Array,
    min_p: jax.Array,
    vocab: int,
) -> jax.Array:
    """Boolean keep mask implementing top-k/top-p/min-p shaping over a
    descending top-KF logit slice. ONE definition shared by sample()'s
    filtered path and the speculative verifier (spec/verify.py) — the
    two must agree exactly or speculative acceptance would target a
    different distribution than non-speculative sampling draws from.

    Probabilities are normalized against the FULL vocab (via ``lse``),
    so the top_p cutoff is exact whenever it falls inside the slice; the
    only approximation is truncating ultra-flat tails (or top_k > KF) to
    the KF most likely tokens."""
    KF = vals.shape[-1]
    ranks = jnp.arange(KF, dtype=jnp.int32)
    k = jnp.where(top_k > 0, top_k, vocab)[..., None]
    k_mask = ranks < k
    sprobs = jnp.exp(vals - lse)  # true full-vocab probabilities
    cum = jnp.cumsum(sprobs, axis=-1)
    p_mask = (cum - sprobs) < top_p[..., None]
    m_mask = sprobs >= (min_p[..., None] * sprobs[..., :1])
    return k_mask & p_mask & m_mask


def sample(
    logits: jax.Array,  # [B, V] f32
    s: dict,  # SamplingBatch.arrays (device-side pytree)
    gen_dense: Optional[jax.Array] = None,  # [B, V] carried counts
    prompt_dense: Optional[jax.Array] = None,
) -> tuple[jax.Array, ...]:
    """Returns (next_tokens [B] i32, logprobs_of_chosen [B] f32); when
    ``s`` carries the "top_lp_n" marker (top-logprobs step variant),
    additionally (top_ids [B, TOPLP_N] i32, top_lps [B, TOPLP_N] f32) —
    the most likely alternatives of the SAME post-bias/penalty
    distribution the chosen logprob is measured on.

    The penalty tables (``gen_dense``/``prompt_dense``) are passed
    explicitly by fused-window callers so the carry survives across
    steps; single-step callers omit them and they are built from the
    sparse tables when present.
    """
    B, V = logits.shape
    rows = jnp.arange(B)[:, None]
    # logit bias first (OpenAI: bias applies before sampling of any
    # kind). Presence-keyed: bias-free batches (the common case) select
    # a variant without the scatter at all.
    if "bias_ids" in s:
        logits = logits.at[rows, s["bias_ids"]].add(s["bias_vals"])
    if "rep_pen" in s:
        if gen_dense is None:
            gen_dense = dense_gen_counts(s, V)
        if prompt_dense is None:
            prompt_dense = dense_prompt_presence(s, V)
        logits = apply_penalties(logits, s, gen_dense, prompt_dense)
    if "allow_mask" in s:
        # guided decoding (docs/guided_decoding.md): disallowed tokens
        # drop to NEG_INF BEFORE the greedy argmax, the filter pipeline,
        # and the logprob computation below, so greedy, seeded sampling,
        # top-k/top-p/min-p, and returned logprobs all see the SAME
        # constrained distribution. Presence-keyed like bias/penalties:
        # unguided batches select the mask-free jit variant.
        logits = jnp.where(s["allow_mask"], logits, NEG_INF)

    temperature, top_k, top_p, min_p, seeds = (
        s["temperature"], s["top_k"], s["top_p"], s["min_p"], s["seeds"]
    )
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # rows that need top-k/top-p/min-p shaping (vs free sampling)
    need_filter = (top_k > 0) | (top_p < 1.0) | (min_p > 0.0)

    def sampled_path(_) -> jax.Array:
        # EXACT free sampling via the gumbel-max trick — NO vocab sort.
        # A full [B, V] argsort per step was ~60% of a fused decode
        # step at V=128k (measured 2.5 s vs 0.95 s windows on v5e) and
        # the OpenAI default (temperature=1, no filters) hits it on
        # every HTTP request.
        temp = jnp.maximum(temperature, 1e-4)[:, None]
        scaled = logits / temp
        keys = jax.vmap(jax.random.key)(seeds)
        gumbel = jax.vmap(
            lambda key, shape=(V,): jax.random.gumbel(key, shape, jnp.float32)
        )(keys)
        free_tok = jnp.argmax(scaled + gumbel, axis=-1).astype(jnp.int32)

        def filtered(_) -> jax.Array:
            # top-k / top-p / min-p shaping on the top-KF slice only.
            # Probabilities are normalized against the FULL vocab
            # (logsumexp over scaled — no sort needed), so the top_p
            # cutoff is exact whenever it falls inside the slice; the
            # only approximation is truncating ultra-flat tails (or
            # top_k > KF) to the KF most likely tokens.
            KF = min(128, V)
            vals, idx = jax.lax.top_k(scaled, KF)  # [B, KF] descending
            lse = jax.nn.logsumexp(scaled, axis=-1, keepdims=True)
            keep = filter_keep_mask(vals, lse, top_k, top_p, min_p, V)
            fvals = jnp.where(keep, vals, NEG_INF)
            g = jnp.take_along_axis(gumbel, idx, axis=-1)
            choice = jnp.argmax(fvals + g, axis=-1)
            return jnp.take_along_axis(idx, choice[:, None], axis=-1)[
                :, 0
            ].astype(jnp.int32)

        # the top-k machinery only runs when some row filters
        sampled_tok = jax.lax.cond(
            jnp.any(need_filter & (temperature > 0.0)),
            filtered,
            lambda _: free_tok,
            None,
        )
        sampled_tok = jnp.where(need_filter, sampled_tok, free_tok)
        is_greedy = temperature <= 0.0
        return jnp.where(is_greedy, greedy_tok, sampled_tok)

    # skip sampling entirely when the whole batch decodes greedily
    # (runtime-dependent branch — both sides compiled, one executes)
    next_tok = jax.lax.cond(
        jnp.all(temperature <= 0.0), lambda _: greedy_tok, sampled_path, None
    )
    logprobs = jax.nn.log_softmax(logits, axis=-1)
    chosen_lp = jnp.take_along_axis(logprobs, next_tok[:, None], axis=-1)[:, 0]
    if "top_lp_n" in s:
        top_lps, top_ids = jax.lax.top_k(logprobs, min(TOPLP_N, V))
        if top_ids.shape[-1] < TOPLP_N:  # tiny test vocabs
            pad = TOPLP_N - top_ids.shape[-1]
            top_ids = jnp.pad(top_ids, ((0, 0), (0, pad)))
            top_lps = jnp.pad(
                top_lps, ((0, 0), (0, pad)), constant_values=NEG_INF
            )
        return next_tok, chosen_lp, top_ids.astype(jnp.int32), top_lps
    return next_tok, chosen_lp


def reference_sample_numpy(
    logits: np.ndarray, s: dict, row: int
) -> np.ndarray:
    """Pure-numpy reference of the logits transform for row ``row`` —
    bias + penalties + filtering masks (no RNG; used by parity tests to
    check the device pipeline's distribution shaping)."""
    x = logits.astype(np.float64).copy()
    if "bias_ids" in s:
        for tok, v in zip(s["bias_ids"][row], s["bias_vals"][row]):
            x[int(tok)] += float(v)
    if "rep_pen" in s:
        gen = np.zeros_like(x)
        for tok, c in zip(s["gen_ids"][row], s["gen_counts"][row]):
            gen[int(tok)] += float(c)
        prompt = np.zeros_like(x)
        for tok, c in zip(s["prompt_ids"][row], s["prompt_counts"][row]):
            prompt[int(tok)] += float(c)
        rp = float(s["rep_pen"][row])
        seen = (gen + prompt) > 0
        x = np.where(seen, np.where(x > 0, x / rp, x * rp), x)
        x = x - float(s["freq_pen"][row]) * gen
        x = x - float(s["pres_pen"][row]) * (gen > 0)
    if "allow_mask" in s:
        x = np.where(s["allow_mask"][row], x, NEG_INF)
    return x
