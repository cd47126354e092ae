"""The layer this family's cell is for, alone, at the timed shape.

``kernel_rel_diff``: the program's window read (`ops.paged_attention` with W
= ``block_length`` query positions a row, 32 query heads over 4 key/value
heads, the pages in place and the W fresh rows handed in, called as
`models/sdar.py` calls it) against the EXPANDED float32 form — every query
head against its key head's rows gathered from the row's pages, the fresh rows
appended, one softmax a head, ``precision=highest`` — on seeded inputs of the
timed shape: the mix's ``rows`` slot rows, the published heads, committed
lengths spread over the cell's range (half the smallest bucket .. ``cache_len``
less a block, block-aligned) with one row that has nothing committed (a dead
row reads the same), the cell's page size, one layer's pool; as ``||got -
want|| / ||want||`` over all rows. The whole model's logits see the read only
through a few dozen steps of eight requests; here it is held over every row of
a full step. On a backend without the kernel (the rehearsal) the gather form
is what the program runs, and that is what is held.
"""

from __future__ import annotations


def layer_checks(config: dict, traffic: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_training_tpu.models.layers import (
        gather_paged_kv, init_paged_kv, scatter_paged_window,
    )
    from distributed_pytorch_training_tpu.models.sdar import (
        attend_window_views,
    )
    from distributed_pytorch_training_tpu.ops.paged_attention import (
        paged_attention, paged_attention_backend_supported,
    )

    sizes, job = config["published"], config["job"]
    heads, kv_heads = sizes["num_attention_heads"], \
        sizes["num_key_value_heads"]
    d, window = sizes["head_dim"], int(job["block_length"])
    rows, ps = int(traffic["rows"]), int(job["page_size"])
    cache_len = max(job["buckets"]) + int(job["max_new_tokens"])
    per_row = -(-cache_len // ps)
    dtype = jnp.bfloat16 if job["serve_dtype"] == "bf16" else jnp.float32

    rng = np.random.default_rng(seed)
    live = rng.integers(min(job["buckets"]) // 2,
                        per_row * ps - window, size=rows)
    live -= live % window                      # a window starts on a block
    live[0] = 0                                # a row with nothing committed
    table = (1 + np.arange(rows * per_row, dtype=np.int32)
             ).reshape(rows, per_row)
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    normal = lambda k, *shape: jax.random.normal(k, shape, jnp.float32)  # noqa: E731
    k_all = normal(keys[0], rows, per_row * ps, kv_heads, d).astype(dtype)
    v_all = normal(keys[1], rows, per_row * ps, kv_heads, d).astype(dtype)
    q = normal(keys[2], rows, window, heads, d).astype(dtype)
    k_new = normal(keys[3], rows, window, kv_heads, d).astype(dtype)
    v_new = normal(keys[4], rows, window, kv_heads, d).astype(dtype)

    @jax.jit
    def fill(k_all, v_all):
        """One layer's pool holding every row's positions, through the
        program's own window scatter."""
        pool = init_paged_kv(1, rows * per_row + 1, ps, kv_heads, d, dtype)
        positions = jnp.broadcast_to(jnp.arange(per_row * ps),
                                     (rows, per_row * ps))
        return scatter_paged_window(
            pool, jnp.asarray(table), positions, k_all[None], v_all[None],
            jnp.ones(positions.shape, bool))

    kernel = paged_attention_backend_supported() and jax.device_count() == 1

    @jax.jit
    def program(pool, q, k_new, v_new, live):
        if kernel:
            flat = lambda x: x.reshape(rows, window, -1)  # noqa: E731
            return paged_attention(
                flat(q), flat(k_new), flat(v_new), pool.k, pool.v,
                jnp.asarray(table), live, layer=0, num_heads=heads,
                num_kv_heads=kv_heads).reshape(q.shape)
        views = tuple(view[0] for view in gather_paged_kv(
            pool, jnp.asarray(table), dtype=dtype))
        return attend_window_views(q, k_new, v_new, views, live, dtype)[0]

    def expanded_row(args):
        """One row's window in the published form, float32."""
        *arrays, n = args
        q_, k_, v_, k_f, v_f = (x.astype(jnp.float32) for x in arrays)
        wide = lambda x: jnp.repeat(x, heads // kv_heads, axis=1)  # noqa: E731
        k_ = jnp.concatenate([wide(k_), wide(k_f)])        # (T + W, H, D)
        v_ = jnp.concatenate([wide(v_), wide(v_f)])
        s = jnp.einsum("whd,thd->hwt", q_, k_) / jnp.sqrt(jnp.float32(d))
        at = jnp.arange(k_.shape[0])
        seen = (at < n) | (at >= k_.shape[0] - window)
        s = jnp.where(seen[None, None, :], s, -jnp.inf)
        return jnp.einsum("hwt,thd->whd", jax.nn.softmax(s, axis=-1), v_)

    @jax.jit
    def reference(q, k_all, v_all, k_new, v_new, live):
        with jax.default_matmul_precision("highest"):
            return jax.lax.map(expanded_row,
                               (q, k_all, v_all, k_new, v_new, live))

    at = jnp.asarray(live, jnp.int32)
    got = program(fill(k_all, v_all), q, k_new, v_new, at)
    want = reference(q, k_all, v_all, k_new, v_new, at)
    diff = jnp.linalg.norm(got.astype(jnp.float32) - want) \
        / jnp.linalg.norm(want)
    return {"kernel_rel_diff": float(diff),
            "kernel_read": "kernel" if kernel else "gather"}
