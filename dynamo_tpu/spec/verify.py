"""Batched on-device verification for speculative decoding.

One call scores a whole batch's draft tokens against the target model's
logits and applies rejection sampling that provably preserves the target
sampling distribution (Leviathan et al., §3.3, specialised to the
deterministic drafters in spec/drafter.py):

- the engine feeds each sequence a ``[S] = [1 + K]`` token run — its
  last committed token followed by up to K draft tokens — through the
  paged-KV prefill attention, getting logits at every position;
- position ``j``'s logits define the target distribution ``p_j`` for the
  sequence's next token (after the same temperature/top-k/top-p/min-p
  shaping ``sample()`` applies — ONE shared keep-mask definition,
  ``engine.sampling.filter_keep_mask``);
- draft ``d_j`` is accepted with probability ``p_j(d_j)`` (the draft
  distribution is a point mass, so the Leviathan acceptance ratio
  ``min(1, p/q)`` reduces to ``p``); greedy rows accept iff
  ``argmax == d_j`` — which makes greedy speculative output
  bit-identical to greedy non-speculative output by construction;
- at the first rejection the replacement token is sampled from the
  residual ``norm(max(0, p - q))`` — for a point-mass q that is ``p``
  with the rejected token masked out, renormalized; if every valid draft
  is accepted, one bonus token is sampled from the next position's
  unmodified ``p``. Either way every step emits at least 1 and at most
  K+1 tokens per sequence.

Distribution preservation (the property tests/test_spec.py checks
statistically): P(emit x at position j) = p_j(x) regardless of what the
drafter proposed — acceptance contributes p(d) mass to d, rejection
contributes (1-p(d)) * p(x)/(1-p(d)) to every other x.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.sampling import NEG_INF, filter_keep_mask


def _shaped_logits(logits_all: jax.Array, s: dict) -> jax.Array:
    """Temperature-scaled, filter-masked logits [B, S, V] — softmax of
    this is the SAME target distribution sample()'s filtered path draws
    from (shared keep mask; see filter_keep_mask)."""
    B, S, V = logits_all.shape
    temperature, top_k, top_p, min_p = (
        s["temperature"], s["top_k"], s["top_p"], s["min_p"]
    )
    temp = jnp.maximum(temperature, 1e-4)[:, None, None]
    scaled = logits_all / temp
    need_filter = (top_k > 0) | (top_p < 1.0) | (min_p > 0.0)

    def filtered(_):
        KF = min(128, V)
        vals, idx = jax.lax.top_k(scaled, KF)  # [B, S, KF] descending
        lse = jax.nn.logsumexp(scaled, axis=-1, keepdims=True)
        keep = filter_keep_mask(
            vals, lse, top_k[:, None], top_p[:, None], min_p[:, None], V
        )
        fvals = jnp.where(keep, vals, NEG_INF)
        b_idx = jnp.arange(B)[:, None, None]
        s_idx = jnp.arange(S)[None, :, None]
        out = jnp.full_like(scaled, NEG_INF).at[b_idx, s_idx, idx].set(fvals)
        return jnp.where(need_filter[:, None, None], out, scaled)

    # the top-k machinery only runs when some row filters
    return jax.lax.cond(
        jnp.any(need_filter), filtered, lambda _: scaled, None
    )


def verify_tokens(
    logits_all: jax.Array,  # [B, S, V] f32 — logits at every fed position
    tokens: jax.Array,  # [B, S] i32 — col 0 = carry token, cols 1.. = drafts
    draft_lens: jax.Array,  # [B] i32 — valid drafts per row (0..S-1)
    s: dict,  # SamplingBatch.arrays (base path only: no penalties/bias)
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (out_tokens [B, S] i32, out_lps [B, S] f32, n_emit [B] i32).

    Row i emits ``out_tokens[i, :n_emit[i]]``: its accepted draft prefix
    followed by one sampled (or argmax) token. ``n_emit - 1`` is the
    accepted-draft count — the accept-rate numerator. ``out_lps`` are
    logprobs of the emitted tokens under log_softmax of the raw target
    logits, matching sample()'s emission semantics exactly.
    """
    B, S, V = logits_all.shape
    K = S - 1
    if "allow_mask" in s:
        # guided decoding (docs/guided_decoding.md): the [B, S, V]
        # per-position allow-mask — position j's mask is the automaton
        # state AFTER the first j drafts commit, computed on host from
        # the SAME automaton that masks the serial path. Applying it
        # here, before argmax/shaping/log_softmax, is the transform
        # sample() applies, at every fed position at once: draft
        # acceptance, replacement sampling, the bonus token, and the
        # emitted logprobs all target the constrained distribution, so
        # speculative verification of structured output is EXACT.
        logits_all = jnp.where(s["allow_mask"], logits_all, NEG_INF)
    temperature, seeds = s["temperature"], s["seeds"]
    greedy = temperature <= 0.0
    logprobs_full = jax.nn.log_softmax(logits_all, axis=-1)
    greedy_tok = jnp.argmax(logits_all, axis=-1).astype(jnp.int32)  # [B, S]
    d = tokens[:, 1:]  # [B, K] draft for output position j

    def sampled_branch(_):
        """Acceptance + replacement sampling for non-greedy rows."""
        shaped = _shaped_logits(logits_all, s)
        shaped_lp = shaped - jax.nn.logsumexp(shaped, axis=-1, keepdims=True)
        lp_d = jnp.take_along_axis(
            shaped_lp[:, :K], d[..., None], axis=-1
        )[..., 0]  # [B, K] log p_j(d_j)

        def per_row(seed):
            key = jax.random.key(seed)
            ku, kg = jax.random.split(key)
            return (
                jax.random.uniform(ku, (K,), jnp.float32),
                jax.random.gumbel(kg, (S, V), jnp.float32),
            )

        u, g = jax.vmap(per_row)(seeds)
        # accept d_j with prob p_j(d_j); log-space comparison avoids
        # exp underflow deciding ties
        accept = jnp.log(jnp.maximum(u, 1e-38)) < lp_d  # [B, K]
        # replacement samples at EVERY position (the emitter selects
        # one): gumbel-max over the shaped logits = exact sampling
        plain = jnp.argmax(shaped + g, axis=-1).astype(jnp.int32)  # [B, S]
        # residual at draft positions: point-mass q removed -> mask the
        # rejected draft and renormalize (gumbel-max needs no explicit
        # renormalization)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, K, V), 2)
        masked = jnp.where(col == d[..., None], NEG_INF, shaped[:, :K])
        resid = jnp.argmax(masked + g[:, :K], axis=-1).astype(jnp.int32)
        return accept, resid, plain

    def greedy_branch(_):
        zeros = jnp.zeros((B, S), jnp.int32)
        return (
            jnp.zeros((B, K), bool), zeros[:, :K], zeros,
        )

    # skip the [B, S, V]-sized sampling machinery when the whole batch
    # decodes greedily (runtime branch — both sides compiled, one runs)
    accept_s, resid, plain = jax.lax.cond(
        jnp.all(greedy), greedy_branch, sampled_branch, None
    )
    accept_g = greedy_tok[:, :K] == d
    accept = jnp.where(greedy[:, None], accept_g, accept_s)
    valid = jnp.arange(K, dtype=jnp.int32)[None, :] < draft_lens[:, None]
    ok = (accept & valid).astype(jnp.int32)
    # accepted-prefix length: stops at the first rejection/invalid slot
    a = jnp.sum(jnp.cumprod(ok, axis=1), axis=1)  # [B] in [0, K]

    def at_a(arr_bs):  # gather each row's column ``a``
        return jnp.take_along_axis(arr_bs, a[:, None], axis=1)[:, 0]

    # replacement token at position a: the residual sample when a valid
    # draft was REJECTED there, the plain sample when all valid drafts
    # were accepted (bonus position). resid is only defined for j < K;
    # a == K implies all-accepted, where plain applies.
    resid_ext = jnp.concatenate([resid, plain[:, K:]], axis=1)  # [B, S]
    rejected_here = a < draft_lens
    final_sampled = jnp.where(rejected_here, at_a(resid_ext), at_a(plain))
    final_tok = jnp.where(greedy, at_a(greedy_tok), final_sampled)

    j_idx = jnp.arange(S, dtype=jnp.int32)[None, :]
    d_ext = jnp.concatenate([d, jnp.zeros((B, 1), d.dtype)], axis=1)
    out_tokens = jnp.where(
        j_idx < a[:, None],
        d_ext,
        jnp.where(j_idx == a[:, None], final_tok[:, None], 0),
    ).astype(jnp.int32)
    out_lps = jnp.take_along_axis(
        logprobs_full, out_tokens[..., None], axis=-1
    )[..., 0]
    n_emit = (a + 1).astype(jnp.int32)
    return out_tokens, out_lps, n_emit


def pack_spec(
    out_tokens: jax.Array, out_lps: jax.Array, n_emit: jax.Array
) -> jax.Array:
    """One packed [B, 2S+1] device array for the verify step's outputs
    — ``[S out_tokens | S out_lps | 1 n_emit]`` per row, token ids and
    emit counts exact in f32 (vocab < 2^24). The twin of the engine's
    ``pack_pair``: every separate device->host read is its own
    synchronisation, so the spec harvest syncs exactly one array per
    step — serial and pipelined alike. ``harvest_spec_output``
    below is the matching (and only) unpacker; the overlapped spec
    pipeline additionally gathers the next step's carry column from
    this layout on device (engine ``chain_spec``)."""
    return jnp.concatenate(
        [
            out_tokens.astype(jnp.float32),
            out_lps,
            n_emit[:, None].astype(jnp.float32),
        ],
        axis=1,
    )


def harvest_spec_output(
    packed, S: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Sync + split the spec step's packed [B, 2S+1] output into
    (out_tokens [B, S] i32, out_lps [B, S] f32, n_emit [B] i32) — token
    ids are exact in f32 (vocab < 2^24), mirroring the fused window's
    packed-transfer idiom. This is the spec path's DESIGNATED HARVEST
    point (dynalint DL010): the one device->host sync of the verify
    step happens here, not inline in the engine step loop."""
    packed_host = np.asarray(packed)
    toks = packed_host[:, :S].astype(np.int32)
    lps = packed_host[:, S : 2 * S]
    n_emit = packed_host[:, 2 * S].astype(np.int32)
    return toks, lps, n_emit
