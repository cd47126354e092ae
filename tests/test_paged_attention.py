"""The paged KV pool read and written in place (ISSUE 25).

Pins, in order:
* `ops.paged_attention` (the kernel read, in interpreter mode on the CPU)
  against `gather_paged_kv` + `decode_dot_product_attention` (the reference
  read) within a stated tolerance and with equal argmax: ragged positions,
  dead rows, rows sharing physical pages, a table full of scratch, the
  fresh row's visibility, fp32 and bf16, every chunking;
* the flattened row scatter all three writes share against the old
  ``store.at[:, page, off]`` form, dropped writes and int8 codes + scales
  included; `paged_kv_bytes` unchanged by the lane-dense shape;
* `SlotEngine.kv_path`: the kernel read only for an unquantized pool on a
  one-device mesh where the backend predicate says so — no option selects
  it; with the predicate monkeypatched a tiny engine emits the reference
  path's greedy tokens over whole requests, its lowered decode step holds
  no tensor of the dense view's shape, carries ``kv_gather`` on the kernel
  call and passes the ``serving_paged`` donation rule; the ``compile`` span
  carries ``kv_path`` and the live-page-share gauge is emitted.
"""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_training_tpu import telemetry
from distributed_pytorch_training_tpu.models.gpt2 import GPT2LMHead
from distributed_pytorch_training_tpu.models.layers import (
    PagedKV, _quant_rows, decode_dot_product_attention, gather_paged_kv,
    init_paged_kv, paged_kv_bytes, scatter_paged_prefill,
    scatter_paged_rows, scatter_paged_window,
)
from distributed_pytorch_training_tpu.parallel import MeshSpec, build_mesh
from distributed_pytorch_training_tpu.serving import continuous
from distributed_pytorch_training_tpu.serving.batching import RequestQueue
from distributed_pytorch_training_tpu.serving.continuous import (
    ContinuousScheduler, SlotEngine,
)
from distributed_pytorch_training_tpu.serving.paged import PagedServeConfig

pa = importlib.import_module(
    "distributed_pytorch_training_tpu.ops.paged_attention")

# the kernel against the reference read, as max|diff| of outputs of O(1):
# fp32 differs by reassociation alone; in bf16 the reference rounds scores,
# weights and its output to 8 bits of mantissa where the kernel keeps
# float32 until its one final rounding
TOL = {jnp.float32: 2e-6, jnp.bfloat16: 3e-2}

L, N_PAGES, PS, H, D, ROWS, P = 3, 30, 4, 2, 8, 6, 4
W = H * D


# ---------------------------------------------------------------------------
# the kernel read against the reference read
# ---------------------------------------------------------------------------


def random_pool(dtype, seed=0):
    rng = np.random.RandomState(seed)
    pool = init_paged_kv(L, N_PAGES, PS, H, D, dtype=dtype)
    return pool.replace(k=jnp.asarray(rng.randn(*pool.k.shape), dtype),
                        v=jnp.asarray(rng.randn(*pool.v.shape), dtype))


def fresh_rows(dtype, seed=1):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(ROWS, W), dtype) for _ in range(3))


def reference_read(q, k_fresh, v_fresh, pool, table, live, layer):
    """What the gather read computes for the same step: the row's pages as
    a dense view, the fresh row written at its position, positions
    <= its own visible."""
    k_all, v_all = gather_paged_kv(pool, table, dtype=q.dtype)
    ck, cv = k_all[layer], v_all[layer]
    t = ck.shape[1]
    hit = (jnp.arange(t)[None, :] == live[:, None])[:, :, None, None]
    ck = jnp.where(hit, k_fresh.reshape(ROWS, 1, H, D), ck)
    cv = jnp.where(hit, v_fresh.reshape(ROWS, 1, H, D), cv)
    mask = (jnp.arange(t)[None, :] <= live[:, None])[:, None, None, :]
    return decode_dot_product_attention(
        q.reshape(ROWS, 1, H, D), ck, cv, mask=mask,
        dtype=q.dtype).reshape(ROWS, W)


def scenario(name):
    """(page table, positions to read per row) of one case."""
    rng = np.random.RandomState(7)
    table = rng.permutation(np.arange(1, N_PAGES))[:ROWS * P].reshape(
        ROWS, P).astype(np.int32)
    last = P * PS - 1               # the table's last position
    live = np.array([0, PS - 1, PS, last, 7, PS + 1], np.int32)
    if name == "dead_rows":         # budget == 0: nothing is read
        live[[1, 3, 4]] = 0
    elif name == "shared_pages":    # a shared prefix: the same physical pages
        table[1, :2] = table[0, :2]
        table[2] = table[3]
        live = np.array([2 * PS, 2 * PS + 1, last, last - 2, 5, 9], np.int32)
    elif name == "all_scratch":     # every entry the scratch page
        table[:] = 0
    return jnp.asarray(table), jnp.asarray(live)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("name", ["ragged", "dead_rows", "shared_pages",
                                  "all_scratch"])
def test_kernel_matches_reference_read(name, dtype):
    pool = random_pool(dtype)
    q, kf, vf = fresh_rows(dtype)
    table, live = scenario(name)
    for layer in (0, L - 1):
        got = pa.paged_attention(q, kf, vf, pool.k, pool.v, table, live,
                                 layer=layer, num_heads=H)
        want = reference_read(q, kf, vf, pool, table, live, layer)
        assert got.dtype == q.dtype and got.shape == (ROWS, W)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=TOL[dtype], rtol=0)
        if dtype == jnp.float32:
            np.testing.assert_array_equal(np.argmax(got, -1),
                                          np.argmax(want, -1))
    if name == "dead_rows":
        # a row that reads nothing attends its own fresh row alone
        dead = np.asarray(live) == 0
        np.testing.assert_array_equal(np.asarray(got)[dead],
                                      np.asarray(vf)[dead])


@pytest.mark.parametrize("pages_per_chunk", [1, 3, 4, None])
def test_kernel_chunking_does_not_change_the_answer(pages_per_chunk):
    """One page a chunk, a chunk that does not divide the row (3 of 4), the
    whole row in one: the same positions, the same answer."""
    pool = random_pool(jnp.float32)
    q, kf, vf = fresh_rows(jnp.float32)
    table, live = scenario("ragged")
    got = pa.paged_attention(q, kf, vf, pool.k, pool.v, table, live,
                             layer=1, num_heads=H,
                             pages_per_chunk=pages_per_chunk)
    want = reference_read(q, kf, vf, pool, table, live, 1)
    np.testing.assert_allclose(got, want, atol=TOL[jnp.float32], rtol=0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
def test_fresh_row_is_visible_and_the_pool_past_it_is_not(dtype):
    """The row's own position comes from the fresh k/v input, never from
    the pool: changing the fresh row changes the answer, changing the pool
    at that position and past it does not."""
    pool = random_pool(dtype)
    q, kf, vf = fresh_rows(dtype)
    table, live = scenario("ragged")
    read = lambda pool, kf, vf: pa.paged_attention(  # noqa: E731
        q, kf, vf, pool.k, pool.v, table, live, layer=0, num_heads=H)
    base = read(pool, kf, vf)
    assert float(jnp.abs(read(pool, kf, vf + 1) - base).max()) > 0.1
    # overwrite every (row, position >= live) of the pool's layer 0
    k5 = np.array(pool.k.astype(jnp.float32)).reshape(L, N_PAGES, PS, W)
    v5 = np.array(pool.v.astype(jnp.float32)).reshape(L, N_PAGES, PS, W)
    for r in range(ROWS):
        for pos in range(int(live[r]), P * PS):
            page = int(table[r, pos // PS])
            k5[0, page, pos % PS] = 1e4
            v5[0, page, pos % PS] = -1e4
    poisoned = pool.replace(k=jnp.asarray(k5, dtype), v=jnp.asarray(v5, dtype))
    np.testing.assert_array_equal(np.asarray(read(poisoned, kf, vf)),
                                  np.asarray(base))


# ---------------------------------------------------------------------------
# the flattened row scatter against the old indexing form
# ---------------------------------------------------------------------------


def old_put(pool: PagedKV, page, off, k_new, v_new) -> PagedKV:
    """The write as it was before the pool was lane-dense:
    ``store.at[:, page, off].set(fresh, mode="drop")`` over (L, n_pages,
    page_size, H, D), a dropped write's page out of range."""
    def put(store, fresh, tail):
        five = store.reshape(store.shape[:3] + tail)
        return five.at[:, page, off].set(fresh.astype(store.dtype),
                                         mode="drop").reshape(store.shape)

    if not pool.quantized:
        return pool.replace(k=put(pool.k, k_new, (H, D)),
                            v=put(pool.v, v_new, (H, D)))
    kq, ks = _quant_rows(k_new, fused=False)
    vq, vs = _quant_rows(v_new, fused=False)
    return pool.replace(k=put(pool.k, kq, (H, D)), v=put(pool.v, vq, (H, D)),
                        k_scale=put(pool.k_scale, ks, (H,)),
                        v_scale=put(pool.v_scale, vs, (H,)))


def assert_pools_equal(a: PagedKV, b: PagedKV):
    for leaf in ("k", "v", "k_scale", "v_scale"):
        x, y = getattr(a, leaf), getattr(b, leaf)
        assert (x is None) == (y is None)
        if x is not None:
            assert x.shape == y.shape and x.dtype == y.dtype
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=leaf)


def _rand(shape, seed):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape)
                       .astype(np.float32))


@pytest.mark.parametrize("quantized", [False, True], ids=["fp32", "int8"])
@pytest.mark.parametrize("which", ["rows", "window", "prefill"])
def test_flattened_put_matches_old_indexing(which, quantized):
    """Same pool bytes as the old form, leaf for leaf (codes AND scales):
    inactive rows, positions past ``length`` and positions past the page
    span are dropped, and a dropped write never lands in another layer."""
    pool = init_paged_kv(L, N_PAGES, PS, H, D, quantized=quantized)
    if not quantized:               # a pool with content: drops must keep it
        pool = random_pool(jnp.float32, seed=3)
    table = jnp.asarray(np.random.RandomState(5).permutation(
        np.arange(1, N_PAGES))[:3 * P].reshape(3, P), jnp.int32)
    if which == "rows":
        positions = jnp.array([0, P * PS - 1, 6], jnp.int32)
        active = jnp.array([True, True, False])
        k, v = _rand((L, 3, H, D), 0), _rand((L, 3, H, D), 1)
        got = scatter_paged_rows(pool, table, positions, k, v, active,
                                 fused=False)
        page = jnp.where(active, table[jnp.arange(3), positions // PS],
                         N_PAGES)
        want = old_put(pool, page, positions % PS, k, v)
    elif which == "window":
        # row 1's window runs off the page span; row 2 is dead
        positions = jnp.array([[0, 1, 2], [P * PS - 2, P * PS - 1, P * PS],
                               [4, 5, 6]], jnp.int32)
        active = (positions < P * PS) & jnp.array([[True], [True], [False]])
        k, v = _rand((L, 3, 3, H, D), 2), _rand((L, 3, 3, H, D), 3)
        got = scatter_paged_window(pool, table, positions, k, v, active,
                                   fused=False)
        page = jnp.where(active, table[jnp.arange(3)[:, None],
                                       positions // PS], N_PAGES)
        want = old_put(pool, page, positions % PS, k, v)
    else:
        length = jnp.int32(2 * PS + 1)      # bucket padding past it drops
        k, v = _rand((L, 3 * PS, H, D), 4), _rand((L, 3 * PS, H, D), 5)
        got = scatter_paged_prefill(pool, table[0], k, v, length,
                                    fused=False)
        idx = jnp.arange(3 * PS)
        page = jnp.where(idx < length, table[0][idx // PS], N_PAGES)
        want = old_put(pool, page, idx % PS, k, v)
    assert_pools_equal(got, want)
    assert not np.array_equal(np.asarray(got.k), np.asarray(pool.k))


@pytest.mark.parametrize("quantized", [False, True], ids=["fp32", "int8"])
def test_paged_kv_bytes_unchanged_by_the_lane_dense_shape(quantized):
    pool = init_paged_kv(L, N_PAGES, PS, H, D, dtype=jnp.bfloat16,
                         quantized=quantized)
    elements = L * N_PAGES * PS * H * D
    want = 2 * elements * (1 if quantized else 2)
    if quantized:                   # one fp32 scale per (position, head)
        want += 2 * (elements // D) * 4
    assert paged_kv_bytes(pool) == want
    assert pool.k.shape == (L, N_PAGES, PS, H * D)


# ---------------------------------------------------------------------------
# the engine: which read the decode step takes, and that both agree
# ---------------------------------------------------------------------------

VOCAB = 97


@pytest.fixture(scope="module")
def mesh1():
    return build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])


@pytest.fixture(scope="module")
def tiny():
    model = GPT2LMHead(vocab_size=VOCAB, hidden_dim=32, depth=2, num_heads=2,
                       max_position=64)
    params = model.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32),
                        train=False)["params"]
    return model, params


def paged_cfg(**kw):
    cfg = dict(buckets=(8, 16), rows=4, max_new_tokens=6, page_size=4)
    cfg.update(kw)
    return PagedServeConfig(**cfg)


@pytest.fixture
def kernel_backend(monkeypatch):
    """A backend on which the kernel read is taken: the ONE predicate,
    patched where the engine asks it; the kernel itself runs in
    interpreter mode, as every Pallas kernel does on the CPU."""
    monkeypatch.setattr(continuous, "paged_attention_backend_supported",
                        lambda backend=None: True)


def test_cpu_takes_the_reference_read(mesh1, tiny):
    assert SlotEngine(tiny[0], mesh1, paged_cfg(), tiny[1]).kv_path == "gather"
    assert not pa.paged_attention_backend_supported()
    assert pa.paged_attention_backend_supported("tpu")


@pytest.mark.parametrize("case", ["fp32_one_device", "int8", "mesh_of_8"])
def test_kv_path_is_read_off_what_the_engine_can_see(case, mesh1, tiny,
                                                     kernel_backend):
    model, params = tiny
    if case == "fp32_one_device":
        assert SlotEngine(model, mesh1, paged_cfg(), params
                          ).kv_path == "kernel"
    elif case == "int8":
        assert SlotEngine(model, mesh1, paged_cfg(kv_dtype="int8"), params
                          ).kv_path == "gather"
    else:
        mesh8 = build_mesh(MeshSpec(), devices=jax.devices())
        assert SlotEngine(model, mesh8, paged_cfg(rows=8), params
                          ).kv_path == "gather"


def test_mosaic_tile_rule(monkeypatch):
    """On a TPU a page has to be whole tiles: 8 sublanes of 4 bytes, 16 of
    2, 128 lanes; the interpreter takes any shape."""
    assert pa.paged_attention_supports(4, 32, jnp.float32)
    monkeypatch.setattr(pa, "_interpret", lambda: False)
    assert pa.paged_attention_supports(16, 768, jnp.bfloat16)
    assert pa.paged_attention_supports(8, 768, jnp.float32)
    assert not pa.paged_attention_supports(8, 768, jnp.bfloat16)
    assert not pa.paged_attention_supports(16, 96, jnp.bfloat16)


def serve_all(engine, prompts, **kw):
    q = RequestQueue(engine.config.buckets)
    sched = ContinuousScheduler(engine, q)
    reqs = [q.submit(p, **kw) for p in prompts]
    sched.drain()
    return [r.result(timeout=300.0) for r in reqs], sched


def test_engine_on_the_kernel_read_emits_the_reference_tokens(
        mesh1, tiny, monkeypatch):
    """Whole requests, mixed lengths, more requests than slots, one prompt
    sent twice (the second is admitted onto resident pages): the greedy
    streams of the two reads are equal and the kept logits agree to fp32
    reassociation."""
    model, params = tiny
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, VOCAB, n).astype(np.int32)
               for n in (3, 8, 9, 16, 5, 12)]
    prompts.append(prompts[1])
    want, _ = serve_all(SlotEngine(model, mesh1, paged_cfg(), params),
                        prompts)
    monkeypatch.setattr(continuous, "paged_attention_backend_supported",
                        lambda backend=None: True)
    engine = SlotEngine(model, mesh1, paged_cfg(), params)
    assert engine.kv_path == "kernel"
    got, sched = serve_all(engine, prompts)
    assert sched.prefill_skips + sched.tail_resumes >= 1
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a.tokens, b.tokens)
        np.testing.assert_allclose(a.last_logits, b.last_logits, atol=1e-5,
                                   rtol=0)


def _lowered_text(engine) -> str:
    return engine.lower_paged_decode().as_text(debug_info=True)


def test_kernel_read_lowers_without_a_dense_view(mesh1, tiny, monkeypatch):
    from benchmark.layer_metrics import _regions

    model, params = tiny
    cfg = paged_cfg()
    engine = SlotEngine(model, mesh1, cfg, params)
    # the dense view is (rows, pages_per_slot * page_size, H, D) a layer
    view = f"{cfg.rows}x{cfg.pages_per_slot * cfg.page_size}x2x16x"
    assert view in _lowered_text(engine)            # the reference read
    monkeypatch.setattr(continuous, "paged_attention_backend_supported",
                        lambda backend=None: True)
    text = _lowered_text(engine)                    # read when it is traced
    assert view not in text
    # the kernel call sits inside the block's attention scope, under
    # `kv_gather`: the region the benchmark's reader files it in
    paths = set(re.findall(r'loc\("(jit\([^"]*)"', text))
    kernel = [p for p in paths if "paged_attention" in p]
    assert kernel, "no scope path names the kernel"
    regions = _regions.PAGED_DECODE[1]
    assert {_regions.region_of(p, regions) for p in kernel} == {"kv_gather"}
    assert all(_regions.region_of(p, ("attn",)) == "attn" for p in kernel)
    assert {_regions.region_of(p, regions) for p in paths} >= set(regions)


@pytest.mark.parametrize("path", ["kernel", "gather"])
def test_serving_paged_contract_passes_on_both_reads(path, mesh1, tiny,
                                                     monkeypatch):
    """`paged-pool-donated`: both pool buffers aliased in place, whichever
    way the step reads them."""
    from distributed_pytorch_training_tpu.analysis.hlo_rules import (
        check_artifacts, paged_serving_artifacts,
    )

    monkeypatch.setattr(continuous, "paged_attention_backend_supported",
                        lambda backend=None: path == "kernel")
    engine = SlotEngine(tiny[0], mesh1, paged_cfg(), tiny[1])
    assert engine.kv_path == path
    artifacts = paged_serving_artifacts(engine)
    assert artifacts.config["paged_cache_leaves"] == 2
    assert check_artifacts(artifacts) == []


def test_compile_span_carries_kv_path_and_page_share_is_gauged(
        mesh1, tiny, kernel_backend):
    model, params = tiny
    rec = telemetry.configure()          # ring-only stream
    try:
        engine = SlotEngine(model, mesh1, paged_cfg(), params)
        serve_all(engine, [np.arange(1, 10, dtype=np.int32)],
                  max_new_tokens=3)
        events = rec.tail(10_000)
    finally:
        telemetry.reset()
    compiles = {e["program"]: e for e in events
                if e["kind"] == "span" and e["name"] == "compile"}
    assert compiles["paged_decode"]["kv_path"] == "kernel"
    assert "kv_path" not in compiles["paged_prefill"]
    share = [e["value"] for e in events if e["kind"] == "gauge"
             and e["name"] == "serving_kv_live_page_share"]
    # 9 prompt + 3 new positions = 3 pages of 4, of 4 rows x 6 pages
    cfg = engine.config
    assert max(share) == pytest.approx(3 / (cfg.rows * cfg.pages_per_slot))
    assert share[-1] == 0.0
