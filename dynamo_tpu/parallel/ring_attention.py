"""Ring attention: exact causal attention over a sequence-sharded mesh axis.

Long-context scaling the reference does NOT have (SURVEY.md §2.6: no
ring/context/sequence parallelism anywhere in the reference — it scales
context only by KV offload + prefill routing). Here it is first-class:
prefill of a sequence too long for one chip's HBM is sharded over the
"sp" mesh axis, with K/V shards rotating around the ring via
``lax.ppermute`` while every device accumulates flash-attention partial
sums (blockwise softmax with running max/denominator, so the result is
exact, not approximate).

Communication rides ICI neighbor links (a ring maps perfectly onto a TPU
torus axis) and overlaps with each step's local attention compute, which
is the standard TPU recipe (jax-ml.github.io/scaling-book). SPMD via
``shard_map``: everything inside is per-shard code with explicit
collectives, so XLA cannot re-layout the ring.

GQA is supported by folding query heads into groups of the KV heads.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _merge(m, l, acc, m_new, l_new, acc_new):
    """Merge two flash-attention partial states (log-sum-exp algebra)."""
    m_out = jnp.maximum(m, m_new)
    a = jnp.exp(m - m_out)
    b = jnp.exp(m_new - m_out)
    return m_out, l * a + l_new * b, acc * a[..., None] + acc_new * b[..., None]


def _block_attend(q, k, v, q_pos, k_pos, scale):
    """Masked local attention block.

    q: [B, Tq, Hk, G, Dh], k/v: [B, Tk, Hk, Dh]. Returns the block's
    flash partials (m, l, acc) with shapes [B, Hk, G, Tq], [...], and
    [B, Hk, G, Tq, Dh].
    """
    s = jnp.einsum(
        "bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    mask = q_pos[:, None] >= k_pos[None, :]  # causal [Tq, Tk]
    s = jnp.where(mask[None, None, None], s, -1e30)
    m = jnp.max(s, axis=-1)  # [B, Hk, G, Tq]
    p = jnp.exp(s - m[..., None])
    p = jnp.where(mask[None, None, None], p, 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v.dtype), v).astype(
        jnp.float32
    )
    return m, l, acc


def ring_attention(
    q: jax.Array,  # [B, T, H, Dh], T sharded over axis_name
    k: jax.Array,  # [B, T, Hk, Dh]
    v: jax.Array,
    mesh: Mesh,
    axis_name: str = "sp",
    scale: Optional[float] = None,
) -> jax.Array:
    """Exact causal attention with sequence sharding. Returns [B, T, H, Dh]
    sharded like q."""
    B, T, H, Dh = q.shape
    Hk = k.shape[2]
    G = H // Hk
    scale = scale if scale is not None else Dh ** -0.5
    n_shards = mesh.shape[axis_name]

    def local(q_l, k_l, v_l):
        # q_l: [B, T_loc, H, Dh] — this device's sequence shard
        T_loc = q_l.shape[1]
        my = jax.lax.axis_index(axis_name)
        qg = q_l.reshape(B, T_loc, Hk, G, Dh)
        q_pos = my * T_loc + jnp.arange(T_loc, dtype=jnp.int32)

        m0 = jnp.full((B, Hk, G, T_loc), -1e30, jnp.float32)
        l0 = jnp.zeros((B, Hk, G, T_loc), jnp.float32)
        a0 = jnp.zeros((B, Hk, G, T_loc, Dh), jnp.float32)

        def attend(i, k_cur, v_cur, m, l, acc):
            src = (my - i) % n_shards  # whose K/V shard we hold this step
            k_pos = src * T_loc + jnp.arange(T_loc, dtype=jnp.int32)
            m_n, l_n, a_n = _block_attend(qg, k_cur, v_cur, q_pos, k_pos, scale)
            return _merge(m, l, acc, m_n, l_n, a_n)

        def step(i, carry):
            k_cur, v_cur, m, l, acc = carry
            m, l, acc = attend(i, k_cur, v_cur, m, l, acc)
            # rotate K/V around the ring (neighbor ICI hop)
            perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]
            k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
            return k_nxt, v_nxt, m, l, acc

        # n_shards-1 rotations suffice: the last-held shard is attended
        # outside the loop, skipping a useless final ICI hop
        k_f, v_f, m, l, acc = jax.lax.fori_loop(
            0, n_shards - 1, step, (k_l, v_l, m0, l0, a0)
        )
        m, l, acc = attend(n_shards - 1, k_f, v_f, m, l, acc)
        out = acc / jnp.maximum(l, 1e-30)[..., None]  # [B, Hk, G, Tq, Dh]
        return out.transpose(0, 3, 1, 2, 4).reshape(B, T_loc, H, Dh).astype(
            q_l.dtype
        )

    spec = P(None, axis_name, None, None)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)


def ulysses_attention(
    q: jax.Array,  # [B, T, H, Dh], T sharded over axis_name
    k: jax.Array,  # [B, T, Hk, Dh]
    v: jax.Array,
    mesh: Mesh,
    axis_name: str = "sp",
    scale: Optional[float] = None,
) -> jax.Array:
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style): reshard
    sequence-sharded Q/K/V to head-sharded full-sequence via one
    ``all_to_all``, attend locally over the whole sequence, then reshard
    back. One collective round-trip instead of ``n_shards`` ring hops —
    wins when heads are plentiful and the axis spans fast ICI; requires
    num (kv) heads divisible by the axis size."""
    B, T, H, Dh = q.shape
    Hk = k.shape[2]
    n = mesh.shape[axis_name]
    if H % n or Hk % n:
        raise ValueError(
            f"ulysses needs H ({H}) and Hkv ({Hk}) divisible by |{axis_name}|={n}"
        )
    scale = scale if scale is not None else Dh ** -0.5

    def to_heads(x_l):  # [B, T_loc, Hx, Dh] -> [B, T, Hx/n, Dh]
        B_, T_loc, Hx, Dh_ = x_l.shape
        x_l = x_l.reshape(B_, T_loc, n, Hx // n, Dh_)
        x_l = jax.lax.all_to_all(
            x_l, axis_name, split_axis=2, concat_axis=1, tiled=False
        )  # [B, T_loc, 1, ...] concat over axis 1 -> [B, T, 1, Hx//n, Dh]
        return x_l.reshape(B_, T_loc * n, Hx // n, Dh_)

    spec_seq = P(None, axis_name, None, None)

    def local(q_l, k_l, v_l):
        T_loc = q_l.shape[1]
        qh, kh, vh = to_heads(q_l), to_heads(k_l), to_heads(v_l)
        out = reference_causal_attention(qh, kh, vh, scale)  # [B, T, H/n, Dh]
        # back: sequence-sharded, all heads. split seq; the received
        # device axis must land chunk-major BEFORE the local-head axis so
        # the reshape restores original head order
        out = out.reshape(B, n, T_loc, H // n, Dh)
        out = jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2)
        return out.reshape(B, T_loc, H, Dh)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec_seq, spec_seq, spec_seq),
        out_specs=spec_seq,
        check_vma=False,
    )(q, k, v)


def reference_causal_attention(q, k, v, scale=None):
    """Single-device exact causal attention (test oracle)."""
    B, T, H, Dh = q.shape
    Hk = k.shape[2]
    G = H // Hk
    scale = scale if scale is not None else Dh ** -0.5
    qg = q.reshape(B, T, Hk, G, Dh)
    s = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32
    ) * scale
    pos = jnp.arange(T, dtype=jnp.int32)
    mask = pos[:, None] >= pos[None, :]
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v.dtype), v)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, H, Dh).astype(q.dtype)
