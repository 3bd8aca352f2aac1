"""The two exact forms of sparse latent attention, timed on the chip at
GLM-5's widths (64 heads over 576-in-640-lane rows, a 32 x 128 indexer,
top 2 048) — what PERF.md section 6 (PR 49) quotes; the benchmark runs
none of it.

    python3 perf/tools/dsa_forms.py [--contexts 8192 14336 20480]

For a decode batch (8 rows) and a 1 024-token chunk at each context:

- ``index`` / ``select``: the two kernels both forms need first
  (``ops/dsa.py``);
- ``walk``: the MASKED WALK the program serves — ``ops/mla.py``'s page
  walk with ``sel``: every live page read, unselected keys masked;
- ``gather``: the other exact form, written here in plain XLA — the
  selected keys' INDICES (``lax.top_k`` of the mask, which sorts: the mask
  alone does not say where its ones are), the selected 640-lane rows
  gathered ``[queries, 2 048, 640]``, attention over them a block of 64
  queries at a time; ``gather_attend`` is that form given the indices
  for nothing.

One JSON line a (phase, context, form): median milliseconds of 5 calls
after a warm-up, ``block_until_ready`` inside the clock.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

H, C, RANK, G, DI, TOPK, BS = 64, 640, 512, 32, 128, 2048, 128
TABLE_W = 200
QUERY_BLOCK = 64


def clock(fn, *args) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times) * 1e3


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--contexts", type=int, nargs="+", default=[8192, 14336, 20480])
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops import dsa
    from dynamo_tpu.ops.mla import mla_decode_attention, mla_prefill_attention

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"dsa_forms: platform {dev.platform}, wanted tpu", file=sys.stderr)
        return 1
    S = TABLE_W * BS
    rng = np.random.default_rng(0)

    def gather_attend(q, latent, slot_ids, picked):
        """q [B, T, H, C]; picked [B, T, k] positions in table order."""
        B, T = q.shape[:2]
        slots = jnp.take_along_axis(slot_ids[:, None, :], picked, axis=-1)

        def block(a):
            qb, sb = a                                     # [B, t, H, C], [B, t, k]
            rows = latent[0, sb]                           # [B, t, k, C]
            s = jnp.einsum("bthc,btkc->bthk", qb, rows,
                           preferred_element_type=jnp.float32)
            p = jax.nn.softmax(s, axis=-1).astype(rows.dtype)
            return jnp.einsum("bthk,btkc->bthc", p, rows[..., :RANK],
                              preferred_element_type=jnp.float32).astype(q.dtype)

        t = min(T, QUERY_BLOCK)
        out = jax.lax.map(block, (
            jnp.moveaxis(q.reshape(B, T // t, t, H, C), 1, 0),
            jnp.moveaxis(slots.reshape(B, T // t, t, TOPK), 1, 0)))
        return jnp.moveaxis(out, 0, 1).reshape(B, T, H, RANK)

    def indices_of(sel):
        return jax.lax.top_k(sel, TOPK)[1]

    for phase, B, T in (("decode", 8, 1), ("prefill", 1, 1024)):
        tables = jnp.asarray(
            1 + np.arange(B * TABLE_W).reshape(B, TABLE_W), jnp.int32)
        slots_n = (B * TABLE_W + 1) * BS
        latent = jnp.asarray(rng.normal(size=(1, slots_n, C)) * 0.3, jnp.bfloat16)
        keys = jnp.asarray(rng.normal(size=(B, S, DI)), jnp.bfloat16)
        slot_ids = (tables[:, :, None] * BS + jnp.arange(BS)).reshape(B, S)
        q_lat = jnp.asarray(rng.normal(size=(B, T, H, C)) * 0.05, jnp.bfloat16)
        q_idx = jnp.asarray(rng.normal(size=(B, T, G, DI)), jnp.bfloat16)
        w = jnp.asarray(rng.normal(size=(B, T, G)), jnp.float32)
        for ctx in args.contexts:
            start = jnp.full((B,), ctx - T, jnp.int32)
            lens = jnp.full((B,), ctx, jnp.int32)
            scores = dsa.index_scores(q_idx, w, keys, start, lens)
            sel = dsa.select_topk(scores, lens, k=TOPK)
            picked = jax.jit(indices_of)(sel)
            # the plane, the table and the contexts are ARGUMENTS: closed over
            # they would be 260 MB of constants in the lowered program
            if phase == "decode":
                def walk(q, s, latent, tables, start, lens):
                    return mla_decode_attention(
                        q[:, 0], latent, jnp.int32(0), tables, lens,
                        block_size=BS, rank=RANK, sel=s[:, 0])

                def dense(q, latent, tables, start, lens):
                    return mla_decode_attention(
                        q[:, 0], latent, jnp.int32(0), tables, lens,
                        block_size=BS, rank=RANK)
            else:
                def walk(q, s, latent, tables, start, lens):
                    return mla_prefill_attention(
                        q, latent, jnp.int32(0), tables, start, lens,
                        block_size=BS, rank=RANK, sel=s)

                def dense(q, latent, tables, start, lens):
                    return mla_prefill_attention(
                        q, latent, jnp.int32(0), tables, start, lens,
                        block_size=BS, rank=RANK)
            walk, dense = jax.jit(walk), jax.jit(dense)
            where = (latent, tables, start, lens)
            given = jax.jit(gather_attend)
            whole = jax.jit(lambda q, s, latent, slot_ids: gather_attend(
                q, latent, slot_ids, indices_of(s)))
            # the two forms agree (bf16 rounding of the probabilities apart)
            a = np.asarray(walk(q_lat, sel, *where), np.float32).reshape(B, T, H, RANK)
            b = np.asarray(given(q_lat, latent, slot_ids, picked), np.float32)
            forms = {
                "index": clock(dsa.index_scores, q_idx, w, keys, start, lens),
                "select": clock(functools.partial(dsa.select_topk, k=TOPK), scores, lens),
                "walk": clock(walk, q_lat, sel, *where),
                "dense_walk": clock(dense, q_lat, *where),
                "gather_attend": clock(given, q_lat, latent, slot_ids, picked),
                "gather": clock(whole, q_lat, sel, latent, slot_ids),
            }
            print(json.dumps({
                "phase": phase, "rows": B, "tokens": T, "context": ctx,
                "selected_per_query": float(np.asarray(sel).sum() / (B * T)),
                "forms_max_abs_diff": float(np.abs(a - b).max()),
                "ms": {k: round(v, 4) for k, v in forms.items()},
                "device": dev.device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
