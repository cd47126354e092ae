"""The layer this family's cell is for, alone, at the timed shape.

``kernel_rel_diff``: the program's absorbed decode read
(`ops.mla_paged_attention`, called as `models/deepseek_v2.py` calls it: the
query with ``W_uk`` folded in, the latent pages in place, ``W_uv`` after)
against the EXPANDED float32 form — every head's 128-wide key and value
built from the latent at every cached position, one softmax a head,
``precision=highest`` — on seeded inputs of the timed shape: the mix's
``rows`` slot rows, the published 128 heads, live lengths spread over the
cell's range (half the smallest bucket .. ``cache_len`` - 1), the cell's
page size, one layer's pool; as ``||got - want|| / ||want||`` over all rows.
The whole model's logits see the absorbed read only through eight decoded
tokens of eight requests; here it is held over every row of a full step.
On a backend without the kernel (the rehearsal) the gather form is what the
program runs, and that is what is held.
"""

from __future__ import annotations


def layer_checks(config: dict, traffic: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference.deepseek_v2 import softmax_scale
    from distributed_pytorch_training_tpu.models.deepseek_v2 import (
        _attend_view,
    )
    from distributed_pytorch_training_tpu.models.layers import (
        gather_paged_kv, init_paged_latent, scatter_paged_window,
    )
    from distributed_pytorch_training_tpu.ops.mla_paged_attention import (
        mla_paged_attention,
    )
    from distributed_pytorch_training_tpu.ops.paged_attention import (
        paged_attention_backend_supported,
    )

    sizes, job = config["published"], config["job"]
    over = config.get("model_overrides", {})
    heads = over.get("num_heads", sizes["num_attention_heads"])
    rank, rope = sizes["kv_lora_rank"], sizes["qk_rope_head_dim"]
    nope, dv = sizes["qk_nope_head_dim"], sizes["v_head_dim"]
    rows, ps = int(traffic["rows"]), int(job["page_size"])
    cache_len = max(job["buckets"]) + int(job["max_new_tokens"])
    per_row = -(-cache_len // ps)
    dtype = jnp.bfloat16 if job["serve_dtype"] == "bf16" else jnp.float32
    scale = softmax_scale(sizes)

    rng = np.random.default_rng(seed)
    live = rng.integers(min(job["buckets"]) // 2, cache_len, size=rows)
    live[0] = 0                                   # a row with nothing cached
    table = (1 + np.arange(rows * per_row, dtype=np.int32)
             ).reshape(rows, per_row)
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = lambda k, *shape: jax.random.normal(k, shape, jnp.float32)  # noqa: E731
    c_all = normal(keys[0], rows, per_row * ps, rank).astype(dtype)
    pe_all = normal(keys[1], rows, per_row * ps, rope).astype(dtype)
    q_nope = normal(keys[2], rows, heads, nope).astype(dtype)
    q_pe = normal(keys[3], rows, heads, rope).astype(dtype)
    w_uk = (normal(keys[4], rank, heads, nope) * rank ** -0.5).astype(dtype)
    w_uv = (normal(keys[5], rank, heads, dv) * rank ** -0.5).astype(dtype)

    @jax.jit
    def fill(c_all, pe_all):
        """One layer's pool holding every row's positions, through the
        program's own window scatter."""
        pool = init_paged_latent(1, rows * per_row + 1, ps, rank, rope, dtype)
        positions = jnp.broadcast_to(jnp.arange(per_row * ps),
                                     (rows, per_row * ps))
        return scatter_paged_window(
            pool, jnp.asarray(table), positions, c_all[None], pe_all[None],
            jnp.ones(positions.shape, bool))

    kernel = paged_attention_backend_supported() and jax.device_count() == 1

    @jax.jit
    def program(pool, q_nope, q_pe, fresh_c, fresh_pe, live):
        q_c = jnp.einsum("bhd,chd->bhc", q_nope, w_uk)
        if kernel:
            o_lat = mla_paged_attention(
                q_c, q_pe, fresh_c, fresh_pe, pool.c, pool.pe,
                jnp.asarray(table), live, layer=0, sm_scale=scale)
        else:
            views = tuple(v[0] for v in gather_paged_kv(
                pool, jnp.asarray(table), dtype=dtype))
            o_lat, _ = _attend_view(q_c, q_pe, fresh_c, fresh_pe, views,
                                    live, scale, dtype)
        return jnp.einsum("bhc,chd->bhd", o_lat.astype(dtype), w_uv)

    def expanded_row(args):
        """One row's attention in the published form, float32."""
        *arrays, n = args
        q_n, q_r, c, pe = (x.astype(jnp.float32) for x in arrays)
        k_nope = jnp.einsum("tc,chd->thd", c, w_uk.astype(jnp.float32))
        v = jnp.einsum("tc,chd->thd", c, w_uv.astype(jnp.float32))
        s = (jnp.einsum("hd,thd->ht", q_n, k_nope)
             + jnp.einsum("hd,td->ht", q_r, pe)) * scale
        s = jnp.where(jnp.arange(c.shape[0])[None, :] <= n, s, -jnp.inf)
        return jnp.einsum("ht,thd->hd", jax.nn.softmax(s, axis=-1), v)

    @jax.jit
    def reference(q_nope, q_pe, c_all, pe_all, live):
        with jax.default_matmul_precision("highest"):
            return jax.lax.map(expanded_row,
                               (q_nope, q_pe, c_all, pe_all, live))

    # the fresh token's row stands at position ``live`` of its row
    at = jnp.asarray(live, jnp.int32)
    fresh_c = c_all[jnp.arange(rows), at]
    fresh_pe = pe_all[jnp.arange(rows), at]
    got = program(fill(c_all, pe_all), q_nope, q_pe, fresh_c, fresh_pe, at)
    want = reference(q_nope, q_pe, c_all, pe_all, at)
    diff = jnp.linalg.norm(got.astype(jnp.float32) - want) \
        / jnp.linalg.norm(want)
    return {"kernel_rel_diff": float(diff),
            "kernel_read": "kernel" if kernel else "gather"}
