"""What the hybrid families share (``models/kimi_linear.py``,
``models/qwen3_next.py``): decoders whose layers are unrolled by kind,
with a per-sequence recurrent state beside the paged rows and an expert
layer that holds a share of the experts.

- the seeded draw and its weight-only int8 form (``init``, ``quantize``,
  the draws of the delta rule's decay parameters);
- a stacked weight's matmul with a float32 result (``mm``, ``weight``,
  ``einsum_f32``, ``gated_mlp``);
- the gated delta rule over a head's ``[d_k, d_v]`` float32 state, a
  token at a time (``delta_decode``) and a chunk at a time
  (``delta_chunked``), with the log-decay a vector over the key
  channels (KDA) or one scalar a head (Gated DeltaNet: minor dimension 1);
- the held experts' part of an expert layer once the family's router has
  chosen (``moe_local``: every held expert over a few rows, or the rows
  sorted by expert through ``ragged_dot``), with its on-device counts;
  what an expert IS — the stacks that read the rows, the activation that
  joins them, the stack that writes back — is the family's to say
  (``ExpertForm``: ``GATED_SILU`` three matrices, ``RELU2`` two);
- the sigmoid router (``sigmoid_routing``) and latent attention with the
  key up-projection absorbed into the queries (``mla_mixer``: NoPE for
  ``kimi_linear``, a rotary part for ``deepseek_v3``);
- what no such family builds yet, refused at start-up (``check_engine``).

A family keeps what is its own: the layer plan, the mixers, the router,
``Geometry``, the cache shapes.
"""

from __future__ import annotations

import contextlib
import math
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from dynamo_tpu.models import llama

Params = dict[str, Any]

DELTA_CHUNK = 64     # tokens per block of the chunked recurrence
GLOBAL = ("embed", "final_norm", "lm_head")   # every other parameter is a stack
MOE_COUNT_NAMES = ("moe_layer_calls", "moe_local_assignments",
                   "moe_experts_touched")


# ---------------------------------------------------------------------------
# The seeded draw
# ---------------------------------------------------------------------------


def draw_normal(key, shape: tuple[int, ...]):
    """``normal / sqrt(fan_in)`` (fan_in: the second-to-last axis)."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(max(1, fan_in))


def draw_A_log(key, shape: tuple[int, ...]):
    """``A_log = log(U(1, 16))``."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


def draw_dt_bias(key, shape: tuple[int, ...], lo: float = 1e-3,
                 hi: float = 1e-1, floor: float = 0.0):
    """The inverse softplus of a log-uniform step in ``[lo, hi]``, held
    at or above ``floor``."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, jnp.float32, math.log(lo), math.log(hi)))
    if floor > 0.0:
        dt = jnp.maximum(dt, floor)
    return dt + jnp.log(-jnp.expm1(-dt))


def quantize(arr, axis: int):
    amax = jnp.max(jnp.abs(arr), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(arr / scale), -127, 127).astype(jnp.int8)
    return q, jnp.squeeze(scale, axis=axis)


def init(shapes: dict, draw_one: Callable, quant_axis: dict, seed: int, mesh,
         quantized: bool, dtype) -> Params:
    """The seeded draw: parameter ``i`` of ``shapes`` (a family's
    ``param_shapes``, whose ORDER is part of the recipe) has key
    ``fold_in(PRNGKey(seed), i)``; a stacked parameter draws layer ``j`` of
    its stack from ``fold_in(., j)`` and, where it holds experts, expert
    ``e`` from ``fold_in(., e)`` again (``models/quant.py``
    ``init_params_quantized``'s order, extended). ``draw_one(name, key,
    shape)`` is the family's rule for one leading slice in float32;
    ``quant_axis`` names the axis the int8 scales reduce over. One slice
    at a time on the device, so the float32 transient is one layer's."""
    root = jax.random.PRNGKey(seed)
    params: Params = {}

    def put(arr):
        if mesh is not None:
            arr = jax.device_put(arr, NamedSharding(mesh, P()))
        return arr

    for i, (name, (shape, want)) in enumerate(shapes.items()):
        key = jax.random.fold_in(root, i)
        axis = quant_axis.get(name) if quantized else None
        out_dtype = want if dtype is None or want == jnp.float32 else dtype

        def leaf(k, shp, name=name, axis=axis, out_dtype=out_dtype):
            arr = draw_one(name, k, shp)
            if axis is not None:
                return quantize(arr, axis)
            return arr.astype(out_dtype), None

        if name in GLOBAL:
            q, s = jax.jit(lambda k, shp=shape: leaf(k, shp))(key)
        else:
            if len(shape) == 4:      # [layers, experts, ., .]
                one = jax.jit(lambda k, shp=shape[2:], n=shape[1]: jax.vmap(
                    lambda e: leaf(jax.random.fold_in(k, e), shp)
                )(jnp.arange(n)))
            else:
                one = jax.jit(lambda k, shp=shape[1:]: leaf(k, shp))
            parts = [one(jax.random.fold_in(key, j)) for j in range(shape[0])]
            q = jnp.stack([p[0] for p in parts])
            s = jnp.stack([p[1] for p in parts]) if axis is not None else None
        params[name] = put(q)
        if s is not None:
            params[name + "_scale"] = put(s)
    return params


def check_engine(config, what: str) -> None:
    """What is not built for a family with recurrent state is refused
    when the engine starts, never served wrong. (A checkpoint is refused
    by ``models/loader.py``, block export and import by
    ``engine.refuse_kv_transfer``, injected embeddings by ``forward``.)"""
    refused = {
        "tensor_parallel_size > 1": config.tensor_parallel_size > 1,
        "expert_parallel_size > 1": config.expert_parallel_size > 1,
        "pipeline_parallel_size > 1": config.pipeline_parallel_size > 1,
        "data_parallel_size > 1": config.data_parallel_size > 1,
        "num_nodes > 1": config.num_nodes > 1,
        "spec_decode (a rejected draft cannot be taken out of the "
        "recurrent state)": bool(config.spec_decode),
        "host_kv_blocks > 0 (KVBM offload moves K/V pages only)":
            config.host_kv_blocks > 0,
        "kv_cache_dtype int8": jnp.dtype(config.kv_cache_dtype) == jnp.int8,
    }
    bad = [name for name, hit in refused.items() if hit]
    if bad:
        raise ValueError(f"{what} does not support: " + "; ".join(bad))


# ---------------------------------------------------------------------------
# Matmuls of a stacked weight
# ---------------------------------------------------------------------------


def kernels_active() -> bool:
    """The families' own Pallas kernels run where the attention kernels
    do: on a TPU, one device (``llama.pallas_attention_active``)."""
    return llama.pallas_attention_active()


# A matmul's RESULT keeps the float32 of its accumulator (its operands
# are the activation dtype): what reads it — a nonlinearity, the float32
# residual stream — rounds once, when it next becomes a matmul's operand,
# and not a second time in between.
MM_OUT = jnp.float32


def mm(p: Params, name: str, x: jax.Array, idx: int) -> jax.Array:
    """x @ p[name][idx] for a stacked weight, in ``MM_OUT``: int8 through
    the ``qmm`` kernel (reads layer ``idx`` in place) where the shape is
    a multiple of 128 both ways, else the mixed-dtype dot; float weights
    plainly."""
    w = p[name]
    out = MM_OUT or x.dtype
    if w.dtype != jnp.int8:
        return einsum_f32("...k,kn->...n", x, w[idx].astype(x.dtype)).astype(out)
    K, N = w.shape[-2:]
    if llama.pallas_matmul_active() and K % 128 == 0 and N % 128 == 0:
        from dynamo_tpu.ops.qmatmul import qmm

        return qmm(x, w, p[name + "_scale"], interpret=llama._qmm_interpret(),
                   layer=jnp.int32(idx), out_dtype=out)
    y = jax.lax.dot_general(
        x, w[idx], (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return (y * p[name + "_scale"][idx]).astype(out)


def weight(p: Params, name: str, idx: int, dtype) -> jax.Array:
    """Layer ``idx`` of a stacked weight, dequantized."""
    w = p[name][idx]
    if w.dtype == jnp.int8:
        return (w.astype(jnp.float32) * p[name + "_scale"][idx]).astype(dtype)
    return w.astype(dtype)


def einsum_f32(eq: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """einsum with float32 accumulation and result. XLA:CPU has no
    bf16 x bf16 -> f32 matmul for every shape: there the operands are
    upcast first, which is exact."""
    if jax.default_backend() == "cpu":
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


def gated_mlp(p: Params, names: tuple[str, str, str], h: jax.Array,
              idx: int) -> jax.Array:
    gate, up, down = names
    mid = jax.nn.silu(mm(p, gate, h, idx)) * mm(p, up, h, idx)
    return mm(p, down, mid.astype(h.dtype), idx)


def relu2(x: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(x))


def relu2_mlp(p: Params, names: tuple[str, str], h: jax.Array,
              idx: int) -> jax.Array:
    """``relu(h U)^2 D``: the ungated two-matrix feed-forward."""
    up, down = names
    return mm(p, down, relu2(mm(p, up, h, idx)).astype(h.dtype), idx)


# ---------------------------------------------------------------------------
# The gated delta rule
# ---------------------------------------------------------------------------


def delta_decode(q, k, v, glog, beta, S):
    """One recurrent update a row. q, k, v [B, H, d]; glog [B, H, d] or
    [B, H, 1]; beta [B, H]; S [B, H, d(key), d(value)] float32. Returns
    (o [B, H, d], S')."""
    with jax.named_scope("delta_decode"), jax.default_matmul_precision("highest"):
        S = jnp.exp(glog)[..., None] * S
        u = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", S, k))
        S = S + k[..., :, None] * u[..., None, :]
        return jnp.einsum("bhkv,bhk->bhv", S, q), S


def delta_chunk_for(rows: int, T: int) -> int:
    """Tokens per block: the [rows, C, C, H, d] decay block is what a
    chunk holds at once, so more rows take shorter blocks."""
    C = DELTA_CHUNK
    while C > 16 and rows * C * C > 8 * DELTA_CHUNK * DELTA_CHUNK:
        C //= 2
    return min(C, T)


def delta_chunked(q, k, v, glog, beta, S, chunk: Optional[int] = None):
    """The same recurrence over T tokens, ``chunk`` at a time. q, k, v
    [B, T, H, d] float32; glog [B, T, H, d], or [B, T, H, 1] where the
    decay is one scalar a head; beta [B, T, H]; S [B, H, d, d].

    With G_t the decay summed from the chunk's start (so every exponent
    below is of G_t - G_j <= 0, t >= j: nothing overflows), the delta
    rule's corrections u_t solve a unit lower-triangular system:
      u_t + beta_t sum_{j<t} A_tj u_j = beta_t (v_t - S0^T (k_t e^{G_t})),
      A_tj = sum_c k_t[c] k_j[c] e^{G_t[c] - G_j[c]};
      o_t = S0^T (q_t e^{G_t}) + sum_{j<=t} B_tj u_j,  B as A with q_t;
      S' = e^{G_C} S0 + sum_j (k_j e^{G_C - G_j}) u_j^T.
    A scalar decay leaves the sum over c: A and B are then plain matrix
    products times e^{G_t - G_j}, and no [C, C, H, d] block exists.
    A token with beta 0 and glog 0 (padding) changes nothing."""
    B, T, H, d = q.shape
    C = min(chunk or delta_chunk_for(B, T), T)
    assert T % C == 0, (T, C)
    per_head = glog.shape[-1] == 1

    def blocks(x):
        return jnp.moveaxis(x.reshape(B, T // C, C, *x.shape[2:]), 1, 0)

    tri = jnp.tril(jnp.ones((C, C), bool))
    strict = jnp.tril(jnp.ones((C, C), bool), -1)

    def body(S, blk):
        qc, kc, vc, gc, bc = blk                      # [B, C, H, .]
        G = jnp.cumsum(gc, axis=1)                    # [B, C, H, d | 1]
        diff = G[:, :, None] - G[:, None, :]          # [B, C(t), C(j), H, d | 1]
        decay = jnp.exp(jnp.where(tri[None, :, :, None, None], diff, -jnp.inf))
        if per_head:
            kk = jnp.einsum("bthk,bjhk->btjh", kc, kc) * decay[..., 0]
            Bm = jnp.einsum("bthk,bjhk->btjh", qc, kc) * decay[..., 0]
            A = jnp.where(strict[None, :, :, None], kk, 0.0)       # [B,C,C,H]
        else:
            kk = kc[:, :, None] * kc[:, None, :] * decay
            A = jnp.where(strict[None, :, :, None], kk.sum(-1), 0.0)   # [B,C,C,H]
            Bm = (qc[:, :, None] * kc[:, None, :] * decay).sum(-1)     # [B,C,C,H]
        eG = jnp.exp(G)
        rhs = bc[..., None] * (vc - jnp.einsum("bhkv,bthk->bthv", S, kc * eG))
        M = jnp.eye(C)[None, :, :, None] + bc[:, :, None, :] * A
        u = jax.scipy.linalg.solve_triangular(
            jnp.moveaxis(M, 3, 1), jnp.moveaxis(rhs, 2, 1), lower=True,
            unit_diagonal=True)                        # [B, H, C, d]
        o = jnp.einsum("bhkv,bthk->bthv", S, qc * eG) + jnp.einsum(
            "btjh,bhjv->bthv", Bm, u)
        last = G[:, -1]                                # [B, H, d | 1]
        S = jnp.exp(last)[..., None] * S + jnp.einsum(
            "bjhk,bhjv->bhkv", kc * jnp.exp(last[:, None] - G), u)
        return S, o

    with jax.named_scope("delta_chunked"), jax.default_matmul_precision("highest"):
        S, o = jax.lax.scan(body, S, tuple(map(blocks, (q, k, v, glog, beta))))
    return jnp.moveaxis(o, 0, 1).reshape(B, T, H, d), S


# ---------------------------------------------------------------------------
# The causal depthwise convolution of a recurrent layer
# ---------------------------------------------------------------------------


def conv_tail_shape(K: int, C: int) -> tuple[int, int]:
    """How a slot's tail — the last ``K - 1`` inputs of ``C`` channels —
    is stored: its rows one after another in rows of one lane tile (of
    ``C`` where ``C`` is no multiple of 128: tests). A slot is then whole
    (8, 128) tiles, contiguous in HBM, that ``ops/conv_tail.py`` moves
    with ONE copy; a ``[., K - 1, C]`` plane pads 3 rows to 8 and is
    copied at the step's edges, and of a flat ``[., (K - 1) C]`` one a
    slot is one sublane of every tile."""
    lane = 128 if C % 128 == 0 else C
    return (K - 1) * C // lane, lane


def conv_step(plane, idx: int, sslot, fresh, n_valid, x, cw, bias=None, *,
              kernels: bool):
    """``y[t] = sum_i full[t + i] * cw[i] (+ bias)`` over this step's
    tokens, ``full`` the slot's tail (zeros for a ``fresh`` row) followed
    by ``x``, and the tail moved on to the last ``K - 1`` VALID inputs.
    ``plane`` [L, slots, *conv_tail_shape] float32, layer ``idx`` of it;
    ``sslot``, ``fresh``, ``n_valid`` [B]; ``x`` [B, T, C] float32; ``cw``
    [K, C]; ``bias`` [C] or None. Returns (y [B, T, C] float32 before
    the activation, plane). A decode step with ``kernels`` (the family's
    ``kernels_active()``) goes through ``ops/conv_tail.py``, which moves
    live rows' tails only; everything else through the XLA lines below,
    which are that kernel's oracle."""
    B, T, C = x.shape
    K = cw.shape[0]
    if T == 1 and kernels:
        from dynamo_tpu.ops.conv_tail import conv_tail_update

        y, plane = conv_tail_update(
            plane, jnp.int32(idx), sslot, fresh, x[:, 0].astype(jnp.float32),
            cw, bias, interpret=jax.default_backend() != "tpu")
        return y[:, None], plane
    tail = jnp.where(fresh[:, None, None], 0,
                     plane[idx, sslot].reshape(B, K - 1, C))
    full = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    y = sum(full[:, i:i + T].astype(jnp.float32) * cw[i] for i in range(K))
    if bias is not None:
        y = bias + y
    # the last K-1 VALID inputs: input j sits at full[j + K - 1]
    rows = n_valid[:, None] + jnp.arange(K - 1)[None, :]
    new_tail = jnp.take_along_axis(full, rows[:, :, None], axis=1)
    plane = plane.at[idx, sslot].set(
        new_tail.reshape(B, *plane.shape[2:]).astype(plane.dtype))
    return y, plane


# ---------------------------------------------------------------------------
# Latent attention (MLA), the key up-projection absorbed into the queries
# ---------------------------------------------------------------------------


class Latent(NamedTuple):
    """The sizes of latent attention. A cached row is ``[c | k_r]``:
    ``rank`` values of the normalised latent, ``rope`` of the key part
    all heads share, stored in ``Cpad`` lanes (whole 128-lane tiles)."""
    H: int
    nope: int
    rope: int
    vd: int
    rank: int
    Cpad: int
    query_tokens: int = 512   # query tokens whose scores exist at once (XLA path)

    @property
    def C(self) -> int:
        return self.rank + self.rope


def rotary_pairs(x: jax.Array, positions: jax.Array, theta: float,
                 interleave: bool) -> jax.Array:
    """``x [B, T, ..., d]`` (float32) rotated at ``positions [B, T]``:
    pair ``i`` of ``d / 2`` turns by ``p * theta^(-2i/d)``. ``interleave``:
    the pairs are the adjacent ``(x_2i, x_2i+1)`` and stay where they are
    (HF's ``apply_rotary_pos_emb_interleave`` moves them to ``(i, i + d/2)``
    first and then uses ``rotate_half``: the same dot products, since q and
    k are permuted alike); else the pairs are ``(x_i, x_i+d/2)``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)     # [d/2]
    ang = positions.astype(jnp.float32)[..., None] * inv             # [B, T, d/2]
    ang = ang.reshape(*positions.shape, *(1,) * (x.ndim - 3), d // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if interleave:
        xp = x.reshape(*x.shape[:-1], d // 2, 2)
        a, b = xp[..., 0], xp[..., 1]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(x.shape)
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def mla_mixer(p: Params, h: jax.Array, mi, latent: jax.Array, m: Latent,
              eps: float, positions: jax.Array, slot_mapping: jax.Array,
              tables: jax.Array, context_lens: jax.Array, block_size: int,
              kernels: bool,
              rotate: Optional[Callable[[jax.Array], jax.Array]] = None,
              flash_prefill: bool = False,
              attend_scope: str = "mla_attend",
              select: Optional[Callable[[jax.Array], jax.Array]] = None):
    """Latent attention of layer ``mi`` of the ``mla_*`` stacks over the
    paged latent plane ``latent [Lm, slots, Cpad]``: ``h [B, T, D]`` ->
    (out [B, T, D] float32, latent with this step's rows written).

    The cached row is ``[c | k_r]`` (``rotate``, where given, has turned
    ``k_r`` — and turns the queries' ``q_r`` — by the token's position,
    so nothing is rotated again when a row is read). Queries absorb the
    key half of ``W_kvb``: attention runs over the cached rows
    themselves, one shared ``rank + rope`` wide "head", and the value
    half is applied after, to the output in latent space. Decode
    (``T == 1``) with ``kernels`` (the family's ``kernels_active()``)
    goes through ``ops/mla.py``
    ``mla_decode_attention``; prefill through ``mla_prefill_attention``
    where ``flash_prefill`` (a tile of query tokens walks its own live
    pages, eight to a compute block, no score ever in HBM: 1.65 us a
    page on a v5e, PERF.md PR 50), else through plain XLA over the
    gathered table,
    ``m.query_tokens`` query tokens at a time.

    A LOW-RANK QUERY where the stacks hold ``mla_wqa`` (``q = W_qb
    RMSNorm(W_qa h)``; else ``q = W_q h``). ``select``, where given, is
    handed the normalised query latent ``c_q`` [B, T, q_lora_rank]
    float32 (``h`` itself without a low-rank query) once this step's rows
    are written and returns marks [B, T, table columns x block_size]
    float32: a query attends only the keys marked > 0.5 — every head
    alike — through the same kernels (``sel``) or the same XLA lines."""
    B, T, _ = h.shape
    act = h.dtype
    c_q = h
    if "mla_wqa" in p:
        c_q = llama.rmsnorm(mm(p, "mla_wqa", h, mi), p["mla_qnorm"][mi], eps)
        q = mm(p, "mla_wqb", c_q.astype(act), mi)
    else:
        q = mm(p, "mla_wq", h, mi)
    q = q.reshape(B, T, m.H, m.nope + m.rope)
    kv = mm(p, "mla_wkva", h, mi)                              # [B, T, C]
    c = llama.rmsnorm(kv[..., : m.rank], p["mla_kvnorm"][mi], eps)
    k_r = kv[..., m.rank:]
    q_r = q[..., m.nope:]
    if rotate is not None:
        k_r, q_r = rotate(k_r), rotate(q_r)
    lane_pad = jnp.zeros((B, T, m.Cpad - m.C), c.dtype)
    row = jnp.concatenate([c, k_r.astype(c.dtype), lane_pad], -1)
    latent = latent.at[mi, slot_mapping].set(
        row.reshape(B * T, m.Cpad).astype(latent.dtype))
    wkvb = weight(p, "mla_wkvb", mi, act).reshape(m.rank, m.H, m.nope + m.vd)
    with jax.named_scope("mla_absorb"):
        q_lat = jnp.concatenate([
            jnp.einsum("bthn,chn->bthc", q[..., : m.nope].astype(act),
                       wkvb[..., : m.nope]),
            q_r.astype(act),
            jnp.zeros((B, T, m.H, m.Cpad - m.C), act)], axis=-1)  # [B, T, H, Cpad]
    root = math.sqrt(m.nope + m.rope)
    interpret = jax.default_backend() != "tpu"

    def scaled(q):     # the softmax scale folded into a kernel's queries
        return (q.astype(jnp.float32) / root).astype(act)

    def out_of(o_lat):
        o = jnp.einsum("bthc,chv->bthv", o_lat, wkvb[..., m.nope:])
        return mm(p, "mla_wo", o.reshape(B, T, m.H * m.vd).astype(act), mi)

    sel = None if select is None else select(c_q)
    marked = {} if sel is None else {"sel": sel[:, 0] if T == 1 else sel}
    if T == 1 and kernels:
        # flash decode over the row's own pages, each read once
        from dynamo_tpu.ops.mla import mla_decode_attention

        # (the dense families' decode call carries no scope: their lowered
        # programs are held to what they were)
        with jax.named_scope(attend_scope) if marked else contextlib.nullcontext():
            o_lat = mla_decode_attention(
                scaled(q_lat[:, 0]), latent, jnp.int32(mi), tables,
                context_lens, block_size=block_size, rank=m.rank,
                interpret=interpret, **marked)[:, None]
        return out_of(o_lat), latent
    if flash_prefill and kernels:
        from dynamo_tpu.ops.mla import mla_prefill_attention

        with jax.named_scope(attend_scope):
            o_lat = mla_prefill_attention(
                scaled(q_lat), latent, jnp.int32(mi), tables, positions[:, 0],
                context_lens,
                block_size=block_size, rank=m.rank, interpret=interpret,
                **marked)
        return out_of(o_lat), latent
    S = tables.shape[1] * block_size
    slot_ids = (tables[:, :, None] * block_size
                + jnp.arange(block_size, dtype=tables.dtype)).reshape(B, S)
    rows = latent[mi, slot_ids].astype(act)                    # [B, S, Cpad]
    key_pos = jnp.arange(S, dtype=jnp.int32)[None, None, None, :]

    def attend(q_blk, pos_blk, sel_blk=None):                  # [B, t, H, C]
        s = einsum_f32("bthc,bsc->bhts", q_blk, rows) * (1.0 / root)
        mask = (key_pos <= pos_blk[:, None, :, None]) & (
            key_pos < context_lens[:, None, None, None])
        if sel_blk is not None:
            mask &= sel_blk[:, None] > 0.5
        pr = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        return jnp.einsum("bhts,bsc->bthc", pr.astype(act),
                          rows[..., : m.rank])

    with jax.named_scope(attend_scope):
        tq = max(1, min(T, m.query_tokens // B))
        if tq >= T or T % tq:
            o_lat = attend(q_lat, positions, sel)
        else:
            qb = jnp.moveaxis(q_lat.reshape(B, T // tq, tq, m.H, m.Cpad), 1, 0)
            pb = jnp.moveaxis(positions.reshape(B, T // tq, tq), 1, 0)
            blocks = (qb, pb) if sel is None else (
                qb, pb, jnp.moveaxis(sel.reshape(B, T // tq, tq, S), 1, 0))
            o_lat = jnp.moveaxis(jax.lax.map(
                lambda a: attend(*a), blocks), 0, 1
            ).reshape(B, T, m.H, m.rank)
    return out_of(o_lat.astype(act)), latent


# ---------------------------------------------------------------------------
# The held experts
# ---------------------------------------------------------------------------


def sigmoid_routing(router: jax.Array, bias: jax.Array, x: jax.Array, k: int,
                    renormalize: bool, scale: float):
    """x [N, D] -> (weights [N, k] float32, expert ids [N, k]) over ALL
    experts: scores sigmoid (float32, ``router [D, E]``), chosen by score
    + selection ``bias``, weighted by the scores themselves, renormalised
    over the chosen, scaled."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(x.astype(jnp.float32) @ router)
    _, topi = jax.lax.top_k(s + bias, k)
    w = jnp.take_along_axis(s, topi, axis=-1)
    if renormalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * scale, topi


class ExpertForm(NamedTuple):
    """What one routed expert computes, by the names of its ``[layers,
    experts, ., .]`` stacks: ``mid(project) @ down``, where
    ``project(name)`` is the rows times stack ``name`` (one of ``reads``)."""
    reads: tuple[str, ...]
    down: str
    mid: Callable[[Callable[[str], jax.Array]], jax.Array]


GATED_SILU = ExpertForm(
    ("we_gate", "we_up"), "we_down",
    lambda project: jax.nn.silu(project("we_gate")) * project("we_up"))
RELU2 = ExpertForm(("we_up",), "we_down", lambda project: relu2(project("we_up")))


def _expert_weights(p: Params, name: str, idx: int, dtype):
    w = p[name][idx]
    scale = p[name + "_scale"][idx] if w.dtype == jnp.int8 else None
    return w.astype(dtype), scale


def moe_local_dense(p: Params, x: jax.Array, combine: jax.Array, idx: int,
                    form: ExpertForm = GATED_SILU):
    """Every held expert over every token, weighted by ``combine`` [N, E]
    (0 where a token did not choose the expert): the form for a few rows,
    where each expert's weights cross HBM once whatever was chosen."""
    def edot(eq, a, name):
        w, scale = _expert_weights(p, name, idx, a.dtype)
        y = einsum_f32(eq, a, w)
        return y if scale is None else y * scale[:, None, :]

    with jax.named_scope("moe_experts"):
        rows = {name: edot("nd,edf->enf", x, name) for name in form.reads}
        mid = form.mid(rows.__getitem__).astype(x.dtype)
        return jnp.einsum("end,ne->nd", edot("enf,efd->end", mid, form.down),
                          combine)


def moe_local_grouped(p: Params, x: jax.Array, w: jax.Array, local_e: jax.Array,
                      idx: int, E: int, form: ExpertForm = GATED_SILU):
    """Assignments sorted by held expert, then grouped matmuls
    (``ragged_dot``) over each expert's run of rows: work and weight
    reads follow the rows assigned. ``local_e`` [N, k]: the held expert's
    index, or E for an assignment another shard holds (sorted last, in no
    group, its weight already 0)."""
    N, k = local_e.shape
    flat = local_e.reshape(-1)
    order = jnp.argsort(flat)
    sorted_e = flat[order]
    xs = jnp.take(x, order // k, axis=0)
    sizes = jnp.bincount(sorted_e, length=E + 1)[:E].astype(jnp.int32)
    held = sorted_e < E
    cpu = jax.default_backend() == "cpu"

    def gdot(a, name):
        # the upcast copy of the layer's experts (XLA does not fuse it
        # into the grouped matmul) must not be made before its rows
        # exist: tied to them, one layer's copies live at a time — left
        # free, the compiler hoists every layer's to the step's start
        # (8 GB at the published widths, refused by the chip's compiler)
        stack, a = jax.lax.optimization_barrier((p[name], a))
        w8 = stack[idx]
        scale = p[name + "_scale"][idx] if w8.dtype == jnp.int8 else None
        wt = w8.astype(jnp.float32 if cpu else a.dtype)
        y = jax.lax.ragged_dot(a.astype(wt.dtype), wt, sizes,
                               preferred_element_type=jnp.float32)
        if scale is not None:
            y = y * jnp.take(scale, jnp.minimum(sorted_e, E - 1), axis=0)
        return y

    with jax.named_scope("moe_experts"):
        mid = form.mid(lambda name: gdot(xs, name))
        out = jnp.where(held[:, None], gdot(mid.astype(x.dtype), form.down), 0)
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(N * k))
    out = jnp.take(out, inv, axis=0).reshape(N, k, -1)
    return jnp.sum(out * w[..., None], axis=1)


def moe_local(p: Params, x: jax.Array, w: jax.Array, topi: jax.Array, idx: int,
              e0: int, E: int, dense_tokens: int,
              valid: Optional[jax.Array] = None,
              form: ExpertForm = GATED_SILU):
    """This process's experts' share of the routed sum. x [N, D]; ``w``,
    ``topi`` [N, k]: the router's weights and choices over ALL experts;
    this process holds experts ``e0 ... e0 + E - 1`` (what other shards'
    experts add is theirs to compute). Up to ``dense_tokens`` rows go
    through every held expert, more are sorted by expert. Returns
    (routed [N, D] float32, counts int32 [3] in ``MOE_COUNT_NAMES``'
    order): this call, the assignments of real tokens (``valid`` [N];
    padding is not traffic) to held experts, and the held experts they
    touched."""
    N = x.shape[0]
    local = (topi >= e0) & (topi < e0 + E)
    w = jnp.where(local, w, 0.0)
    local_e = jnp.where(local, topi - e0, E)
    real = local if valid is None else local & valid.reshape(N, 1)
    touched = jnp.zeros((E + 1,), jnp.int32).at[
        jnp.where(real, local_e, E)].max(1)[:E]
    counts = jnp.stack([jnp.int32(1), jnp.sum(real, dtype=jnp.int32),
                        jnp.sum(touched, dtype=jnp.int32)])
    if N <= dense_tokens:
        combine = jnp.zeros((N, E + 1), jnp.float32).at[
            jnp.arange(N)[:, None], local_e].add(w)[:, :E]
        routed = moe_local_dense(p, x, combine, idx, form)
    else:
        routed = moe_local_grouped(p, x, w, local_e, idx, E, form)
    return routed, counts
