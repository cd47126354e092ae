"""chip_smoke.py — does the system still start, and run right, on the chip?

One process, the only one that touches JAX, drives the main path once through
the entry points a user calls, on every device `jax.devices()` returns (so the
same file is the one-chip and the four-chip check), with `gpt2_124m` at full
width and weights made from a seed:

  0. device   the backend is a TPU — anything else exits non-zero, naming
              what was found (``JAX_PLATFORMS=cpu`` included: this script
              has no CPU mode);
  1. kernels  the Pallas kernels, compiled by Mosaic, against their
              references on the chip: flash attention forward + gradient at
              the shapes phase 2 trains at and at BERT's, the two int8
              codec kernels against the XLA-composed codec, the Gated
              DeltaNet mixer's convolution pair against its XLA form at the
              hybrid cell's shape, and the window read of the paged pool
              (W 4, 32 query heads over 4 key/value heads of 128, pages of
              64, a row with nothing committed) against the expanded
              float32 form at the block-diffusion cell's shape, and the
              grouped product of that cell's expert layer (5,632 sorted
              rows of 2,048 by 128 experts' 2,048 x 768, group sizes by the
              cell's routing rule) against float32 products at `highest`,
              and the held-experts layer's row operators (`models.moe`'s
              `take_rows` / `sum_rows`) at the hybrid cell's timed shape
              (8,192 tokens x 10, a window of 20,480 rows of 2,048, bf16)
              against the gather and the scatter-add they replaced;
  2. train    ``train.main([...])``: eight optimizer steps + validation +
              a manifest-verified checkpoint;
  3. serve    the token-granular server (SlotEngine + PagePool) from that
              checkpoint through ``serving.__main__.main([...])``, fp32
              pages then int8 pages.

Every phase is timed and fatal: a phase that raises, or a check that fails,
ends the run with a non-zero exit and no result line. On success the last
line of stdout is ``{"ok": true, "device": {...}}`` with the device as JAX
reports it; the line before it is the JSON summary (seconds and compile
seconds per phase, compile-cache hits, the previous run's compile seconds
when one ran in this checkout before). It measures nothing a benchmark
would claim — ``"claim": null``.

Run:  python chip_smoke.py          (no arguments; writes ./chip_smoke_out/)
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import re
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT))
OUT = ROOT / "chip_smoke_out"

# phase 2/3 sizes: gpt2_124m at full width (hidden 768, depth 12, 12 heads)
SEQ_LEN = 1024
PER_DEVICE_BATCH = 8
TRAIN_STEPS = 8
SERVE_REQUESTS = 8
SERVE_NEW_TOKENS = 8

# Kernel tolerances (phase 1). Flash runs in bf16 against an fp32 reference
# computed from the same bf16 inputs: the error budget is bf16's 2^-8
# rounding of the output plus the MXU's bf16 operand passes, judged as
# max|kernel - ref| / max|ref| per tensor.
FLASH_REL_TOL = 2e-2
# The codecs are exact integer grids: a code may differ from the XLA codec's
# by one step only where x/scale lands within an ulp of a rounding boundary
# (the kernel divides, XLA may multiply by a reciprocal); scales and
# dequantized sums agree to fp32 rounding.
CODE_MAX_DIFF = 1
CODE_DIFF_FRACTION = 1e-3
FP32_REL_TOL = 1e-6
# The mixer's convolution pair is float32 inside and rounds a bf16 table
# once: against the XLA form in float32 from the same bf16 inputs a table
# sits within bf16's rounding, 2^-8, as ||kernel - ref|| / ||ref||; the
# taps' gradient is a float32 sum over 8,192 rows in another order.
CONV_TABLE_TOL = 2 ** -8
CONV_TAPS_TOL = 1e-4
# The grouped product takes bf16 rows and weights, sums in float32 and
# rounds once: against float32 products at `highest` from the same bf16
# values, as ||kernel - ref|| / ||ref||, it reads 1.66e-3, bf16's rounding
# of the result, and so does `lax.ragged_dot`; with rows and weights rounded
# to float8_e4m3 first, the nearest precision below, 0.313, most of the
# 0.02-scaled weights lying under its least normal number (my chip run, PR
# 43, call 2). The limit lies between, nearer the first.
GROUPED_PRODUCT_TOL = 8e-3
# `sum_rows` adds a token's bf16 rows in float32 and rounds once: against
# the float32 scatter-add of the same rows, as ||got - want|| / ||want||, it
# reads 1.16e-3, bf16's rounding of the result (the bf16 scatter-add it
# replaced reads 1.16e-3 too: most tokens have one row in a window); with
# the rows rounded to float8_e4m3 first (`lax.reduce_precision`), the
# nearest precision below, 2.69e-2 (my chip run, PR 47, call 2). The limit
# is their geometric middle.
ROW_SUM_TOL = 6e-3


class SmokeFailure(Exception):
    """A check that did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


class _Tee(io.TextIOBase):
    """stdout that also keeps what was written (the CLIs print their
    results; the smoke checks what they printed)."""

    def __init__(self, stream):
        self.stream = stream
        self.kept = io.StringIO()

    def write(self, text):
        self.kept.write(text)
        return self.stream.write(text)

    def flush(self):
        self.stream.flush()


@contextlib.contextmanager
def tee_stdout():
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        yield tee.kept


class CompileMeter:
    """Seconds this process spent in XLA's backend compile (a persistent-
    cache hit counts its retrieval time there) and the cache's hit/miss
    census, from jax's own monitoring events."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def phase_device() -> dict:
    import importlib.metadata

    import jax
    import jaxlib

    devices = jax.devices()
    found = sorted({f"{d.platform}:{d.device_kind}" for d in devices})
    if jax.default_backend() != "tpu" or any(
            d.platform != "tpu" for d in devices):
        print(f"chip_smoke: no TPU — jax.default_backend()="
              f"{jax.default_backend()!r}, devices {found} "
              f"(jax_platforms={jax.config.jax_platforms!r}); this script "
              "runs on the chip only", file=sys.stderr, flush=True)
        sys.exit(1)

    from distributed_pytorch_training_tpu import native
    from distributed_pytorch_training_tpu.runtime import (
        CACHE_DIR_ENV, compile_cache_dir, enable_persistent_compile_cache,
    )

    check(enable_persistent_compile_cache(), "persistent compile cache on")
    print(f"  device_kind={devices[0].device_kind} count={len(devices)} "
          f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={importlib.metadata.version('libtpu')} "
          f"jax_platforms={jax.config.jax_platforms!r}\n"
          f"  compile cache: {compile_cache_dir()} "
          f"({CACHE_DIR_ENV} {'set' if os.environ.get(CACHE_DIR_ENV) else 'unset'})\n"
          f"  host data path: {native.describe()}", flush=True)
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def _rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def phase_kernels() -> None:
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_training_tpu.ops.quantize import (
        dequant_sum_rows_fused, quantize_int8_rows_fused,
    )
    from distributed_pytorch_training_tpu.parallel.grad_sync import (
        _dequant_sum_rows, _quantize_int8_rows,
    )

    # the module, not the function `ops` re-exports under the same name
    fa = importlib.import_module(
        "distributed_pytorch_training_tpu.ops.flash_attention")

    def flash_case(name, b, s, h, d, causal, masked):
        q, k, v, w = (jax.random.normal(kk, (b, s, h, d), jnp.bfloat16)
                      for kk in jax.random.split(jax.random.PRNGKey(s), 4))
        # key-padding mask: each row keeps a different prefix of its keys
        kv_valid = ((jnp.arange(s)[None, :]
                     < jnp.asarray([s - 37, s // 2])[:b, None])
                    .astype(jnp.float32) if masked else None)
        scale = 1.0 / np.sqrt(d)

        def kernel_loss(q, k, v):
            out = fa.flash_attention(q, k, v, causal, None, 512, 512,
                                     kv_valid)
            return (out.astype(jnp.float32)
                    * w.astype(jnp.float32)).sum(), out

        def ref_loss(q, k, v):
            with jax.default_matmul_precision("highest"):
                out = fa._reference_attention(
                    q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32), causal, scale, kv_valid)
            return (out * w.astype(jnp.float32)).sum(), out

        (_, out), grads = jax.jit(jax.value_and_grad(
            kernel_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        (_, out_ref), grads_ref = jax.jit(jax.value_and_grad(
            ref_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        errs = {"out": _rel_err(out, out_ref)}
        for n, g, gr in zip(("dq", "dk", "dv"), grads, grads_ref):
            errs[n] = _rel_err(g, gr)
        check(all(np.isfinite(e) and e <= FLASH_REL_TOL
                  for e in errs.values()),
              f"flash {name} (B={b} S={s} H={h} D={d} bf16) within "
              f"{FLASH_REL_TOL:g} of the fp32 reference: "
              + " ".join(f"{n}={e:.2e}" for n, e in errs.items()))

    flash_case("causal", 2, SEQ_LEN, 12, 64, causal=True, masked=False)
    flash_case("bert kv_valid", 2, 512, 12, 64, causal=False, masked=True)

    def codec_case(n, s):
        x = jax.random.normal(jax.random.PRNGKey(n), (n, s), jnp.float32) \
            * jnp.exp(jax.random.normal(jax.random.PRNGKey(s), (n, 1)))
        q, scales = jax.jit(quantize_int8_rows_fused)(x)
        q_ref, scales_ref = jax.jit(
            lambda r: _quantize_int8_rows(r, fused=False))(x)
        diff = np.abs(np.asarray(q, np.int32) - np.asarray(q_ref, np.int32))
        n_diff = int(np.count_nonzero(diff))
        scale_err = _rel_err(scales, scales_ref)
        check(int(diff.max()) <= CODE_MAX_DIFF
              and n_diff <= CODE_DIFF_FRACTION * diff.size
              and scale_err <= FP32_REL_TOL,
              f"quantize_int8_rows_fused ({n}, {s}) vs the XLA codec: "
              + ("codes bitwise" if n_diff == 0 else
                 f"{n_diff}/{diff.size} codes differ, by at most "
                 f"{int(diff.max())}")
              + (", scales bitwise" if scale_err == 0.0 else
                 f", scales within {scale_err:.1e}"))
        total = jax.jit(dequant_sum_rows_fused)(q_ref, scales_ref)
        total_ref = jax.jit(lambda q, sc: _dequant_sum_rows(
            q, sc, fused=False))(q_ref, scales_ref)
        sum_err = _rel_err(total, total_ref)
        check(sum_err <= FP32_REL_TOL,
              f"dequant_sum_rows_fused ({n}, {s}) vs jnp.sum: "
              + ("bitwise" if np.array_equal(np.asarray(total),
                                             np.asarray(total_ref))
                 else f"within {sum_err:.1e} of max|sum|"))

    def conv_case(b, s, conv_dim, z_dim):
        from distributed_pytorch_training_tpu.ops.gated_delta_rule import (
            causal_conv_silu,
        )
        from distributed_pytorch_training_tpu.ops.gdn_conv_kernels import (
            conv_silu_backward, conv_silu_forward,
        )

        ks = jax.random.split(jax.random.PRNGKey(conv_dim), 4)
        width = conv_dim + z_dim
        qkvz, into = (jax.random.normal(k, (b, s, width), jnp.bfloat16)
                      for k in ks[:2])
        dout = jax.random.normal(ks[2], (b, s, conv_dim), jnp.bfloat16)
        taps = jax.random.uniform(ks[3], (4, conv_dim), jnp.float32,
                                  -0.5, 0.5)
        out = jax.jit(conv_silu_forward)(qkvz, taps)
        # the table's cotangent as the rule's backward leaves it: q | k | v
        # at 16, 16 and 32 heads of conv_dim / 64 columns
        cuts = (0, conv_dim // 4, conv_dim // 2, conv_dim)
        dqkvz, dtaps = jax.jit(conv_silu_backward)(
            qkvz, taps, tuple(dout[..., lo:hi] for lo, hi
                              in zip(cuts, cuts[1:])), into)

        out_ref, vjp = jax.vjp(
            causal_conv_silu, qkvz[..., :conv_dim].astype(jnp.float32), taps)
        dx_ref, dtaps_ref = jax.jit(vjp)(dout.astype(jnp.float32))

        def norm_err(got, want):
            got, want = (np.asarray(x, np.float32) for x in (got, want))
            return float(np.linalg.norm(got - want) / np.linalg.norm(want))

        errs = {"out": norm_err(out, out_ref),
                "dqkv": norm_err(dqkvz[..., :conv_dim], dx_ref),
                "dtaps": norm_err(dtaps, dtaps_ref)}
        kept = bool(jnp.array_equal(dqkvz[..., conv_dim:],
                                    into[..., conv_dim:]))
        check(kept and all(np.isfinite(e) for e in errs.values())
              and errs["out"] <= CONV_TABLE_TOL
              and errs["dqkv"] <= CONV_TABLE_TOL
              and errs["dtaps"] <= CONV_TAPS_TOL,
              f"gdn_conv_fwd / gdn_conv_bwd (B={b} S={s}, {conv_dim} of "
              f"{width} columns, bf16) against the XLA form in float32: "
              + " ".join(f"{n}={e:.2e}" for n, e in errs.items())
              + (", z's columns kept bitwise" if kept
                 else ", z's columns CHANGED"))

    def window_case(cell):
        """The cell's own layer check (benchmark/checks/sdar.py), under the
        cell's own limit: a standing check of the kernel on a device
        besides the cell."""
        from benchmark.checks.sdar import layer_checks
        from benchmark.run import load_cell

        _, _, config, mix = load_cell(cell, rehearsal=False)
        got = layer_checks(config, mix, seed=42)
        limit = config["correct"]["kernel_rel_diff_tol"]
        check(got["kernel_read"] == "kernel"
              and got["kernel_rel_diff"] <= limit,
              f"paged_attention window read ({mix['rows']} rows x W "
              f"{config['job']['block_length']}, read {got['kernel_read']}) "
              f"against the expanded float32 form: "
              f"{got['kernel_rel_diff']:.2e} of ||want|| (limit {limit})")

    def grouped_product_case(tokens, hidden, width, experts, top_k):
        """`ops.grouped_product` at the block-diffusion cell's timed shape,
        the group sizes what the cell's routing rule (the ``top_k`` best of
        ``experts`` by a router of scale 0.1) gives seeded rows, against
        float32 products at `highest`, one expert at a time."""
        from distributed_pytorch_training_tpu.ops.grouped_product import (
            grouped_product, grouped_product_supports,
        )

        ks = jax.random.split(jax.random.PRNGKey(43), 3)
        x = jax.random.normal(ks[0], (tokens, hidden), jnp.float32)
        router = 0.1 * jax.random.normal(ks[1], (hidden, experts))
        chosen = jax.lax.top_k(x @ router, top_k)[1].reshape(-1)
        order = jnp.argsort(chosen, stable=True)
        sizes = np.bincount(np.asarray(chosen), minlength=experts)
        rows = x[order // top_k].astype(jnp.bfloat16)
        weights = (0.02 * jax.random.normal(
            ks[2], (experts, hidden, width))).astype(jnp.bfloat16)
        check(grouped_product_supports(*rows.shape, width, rows.dtype),
              f"grouped_product takes {rows.shape} by {weights.shape}")
        got = np.asarray(jax.jit(grouped_product)(
            rows, weights, jnp.asarray(sizes, jnp.int32)), np.float32)
        # one program for every group: the longest group's rows from the
        # group's start, of which the group's own are kept
        longest = int(sizes.max())
        exact = jax.jit(lambda a, w, start, g: jnp.dot(
            jax.lax.dynamic_slice_in_dim(a, start, longest).astype(
                jnp.float32), w[g].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        padded = jnp.pad(rows, ((0, longest), (0, 0)))
        starts = np.cumsum(sizes) - sizes
        want = np.concatenate([
            np.asarray(exact(padded, weights, start, g))[:size]
            for g, (size, start) in enumerate(zip(sizes, starts))])
        err = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        check(np.isfinite(err) and err <= GROUPED_PRODUCT_TOL,
              f"grouped_product ({rows.shape[0]} rows x {hidden} by "
              f"{experts} x {hidden} x {width}, bf16, groups of "
              f"{sizes.min()}..{sizes.max()}) against float32 products at "
              f"highest: {err:.2e} of ||want|| (limit "
              f"{GROUPED_PRODUCT_TOL})")

    def row_operators_case(tokens, top_k, hidden, experts, held):
        """`models.moe.take_rows` / `sum_rows` at the hybrid cell's timed
        shape: the first window of the sorted order as `HeldExpertsMoe`
        makes it (a quarter of ``tokens * top_k`` rows, the held ones a
        prefix) from seeded scores. `take_rows` against ``xf[token]``
        masked, bit for bit; `sum_rows` and the bf16 scatter-add it
        replaced against the float32 scatter-add of the same rows, and
        `sum_rows` again on rows rounded to float8_e4m3; a call's time in
        both forms, eight calls a program."""
        from distributed_pytorch_training_tpu.models.moe import (
            sum_rows, take_rows,
        )

        every, rows = tokens * top_k, tokens * top_k // 4
        ks = jax.random.split(jax.random.PRNGKey(47), 3)
        chosen = jax.lax.top_k(
            jax.random.normal(ks[0], (tokens, experts)), top_k)[1]
        order = jnp.argsort(chosen.reshape(every), stable=True).astype(
            jnp.int32)
        n_held = int((chosen < held).sum())
        check(0 < n_held < rows, f"{n_held} of {every} assignments held: "
              f"inside the first window of {rows}")
        rank_by_choice = jnp.argsort(order).astype(jnp.int32).reshape(
            tokens, top_k).T
        start, stop = jnp.int32(0), jnp.int32(n_held)
        token, in_group = order[:rows] // top_k, jnp.arange(rows) < n_held
        xf = jax.random.normal(ks[1], (tokens, hidden), jnp.bfloat16)
        out = jax.random.normal(ks[2], (rows, hidden), jnp.bfloat16)

        def scatter_add(out, dtype):
            return jnp.zeros((tokens, hidden), dtype).at[token].add(
                jnp.where(in_group[:, None], out, 0).astype(dtype))

        def ms(fn, first):
            scaled = [first * (1 + i / 64) for i in range(8)]
            many = jax.jit(lambda xs: [fn(x) for x in xs])
            jax.block_until_ready(many(scaled))
            t0 = time.perf_counter()
            for _ in range(5):
                got = many(scaled)
            jax.block_until_ready(got)
            return (time.perf_counter() - t0) / 40 * 1e3, got[0]

        gathered = jax.jit(lambda x: take_rows(
            x, token, rank_by_choice, start, stop))(xf)
        check(bool((gathered == jnp.where(in_group[:, None], xf[token],
                                          0)).all()),
              f"take_rows ({rows} rows of {hidden} from {tokens} tokens, "
              f"bf16, {n_held} held) is xf[token], zeros past the last "
              "group, bit for bit")
        want = np.asarray(jax.jit(lambda o: scatter_add(o, jnp.float32))(out))
        t_sum, got = ms(lambda o: sum_rows(o, token, rank_by_choice, start,
                                           stop), out)
        t_scatter, old = ms(lambda o: scatter_add(o, jnp.bfloat16), out)
        coarse = jax.jit(lambda o: sum_rows(jax.lax.reduce_precision(
            o, exponent_bits=4, mantissa_bits=3), token, rank_by_choice,
            start, stop))(out)
        err, err_old, err_coarse = (
            float(np.linalg.norm(np.asarray(a, np.float32) - want)
                  / np.linalg.norm(want)) for a in (got, old, coarse))
        check(np.isfinite(err) and err <= ROW_SUM_TOL < err_coarse,
              f"sum_rows ({rows} rows of {hidden} into {tokens} tokens x "
              f"{top_k}, bf16) against the float32 scatter-add: {err:.2e} "
              f"of ||want|| (limit {ROW_SUM_TOL}; the bf16 scatter-add "
              f"{err_old:.2e}, float8_e4m3 rows {err_coarse:.2e}); "
              f"{t_sum:.3f} ms a call, the scatter-add {t_scatter:.3f}")

    codec_case(1, 25 * 2 ** 20 // 4)      # one 25 MB gradient bucket
    codec_case(4, 25 * 2 ** 20 // 16)     # its four multihop chunks
    codec_case(4, 100_003)                # a length no block divides
    # train_qwen3_next_s8192_1chip's: q | k | v of 16 + 16 + 32 heads of 128
    conv_case(1, 8192, 8192, 4096)
    # and its expert layer's rows: the 10 best of 512, 32 of them held
    row_operators_case(8192, 10, 2048, 512, 32)
    if jax.device_count() == 1:    # the kernel read is a one-device program
        window_case("serve_sdar_block_diffusion_batch")
        # its expert layer's products: 176 rows x W 4, the 8 best of 128
        grouped_product_case(704, 2048, 768, 128, 8)


def phase_train(n_devices: int) -> Path:
    import numpy as np

    import train
    from distributed_pytorch_training_tpu.training.checkpoint import (
        CheckpointManager,
    )

    out_dir, ckpt_dir = OUT / "train", OUT / "ckpt"
    global_batch = PER_DEVICE_BATCH * n_devices
    with tee_stdout() as printed:
        train.main([
            "--model", "gpt2_124m", "--seq-len", str(SEQ_LEN), "--amp",
            "--optimizer", "adamw", "--lr", "3e-4", "--attention", "auto",
            "--batch-size", str(PER_DEVICE_BATCH), "--epochs", "1",
            "--synthetic-size", str(TRAIN_STEPS * global_batch),
            "--print-freq", "1",
            "--checkpoint-dir", str(ckpt_dir), "--output-dir", str(out_dir),
        ])
    text = printed.getvalue()

    check(f"world_size={n_devices}," in text and "Using device: tpu:" in text,
          f"trainer banner: tpu, world_size={n_devices}")
    check("Kernels: attention=flash (--attention auto)" in text,
          "--attention auto resolved to flash")
    losses = [float(m) for m in re.findall(r"Step \[\d+/\d+\] Loss: (\S+)",
                                           text)]
    check(len(losses) == TRAIN_STEPS and bool(np.all(np.isfinite(losses))),
          f"{TRAIN_STEPS} logged step losses, all finite "
          f"({losses[0]:.3f} -> {losses[-1]:.3f})")
    epoch = re.search(r"\[Epoch 1/1\] Train: loss=(\S+), acc=\S+ \| "
                      r"Val: loss=(\S+),", text)
    check(epoch is not None
          and bool(np.all(np.isfinite([float(g) for g in epoch.groups()]))),
          "epoch summary with finite train and validation loss")

    events = [json.loads(line) for line in
              (out_dir / "telemetry_rank0.jsonl").read_text().splitlines()]
    steps = [e for e in events if e.get("name") == "steps"]
    check(len(steps) == 1 and steps[0]["value"] == TRAIN_STEPS,
          f"telemetry `steps` counter == {TRAIN_STEPS}")
    paths = [e for e in events if e.get("name") == "kernel_paths"]
    check(len(paths) == 1 and paths[0]["attention"] == "flash",
          "telemetry kernel_paths event says flash")
    rows = (out_dir / "metrics_rank0.csv").read_text().strip().splitlines()
    check(len(rows) == 2, "metrics_rank0.csv has its header and one row")

    ckpt = CheckpointManager(str(ckpt_dir))
    try:
        manifest = ckpt.manifest(TRAIN_STEPS)
        check(manifest is not None and ckpt.verify(TRAIN_STEPS) is None
              and manifest["step"] == TRAIN_STEPS,
              f"checkpoint {TRAIN_STEPS} verifies against its manifest and "
              f"holds optimizer step {TRAIN_STEPS}")
    finally:
        ckpt.close()
    return ckpt_dir


def phase_serve(ckpt_dir: Path) -> None:
    from distributed_pytorch_training_tpu.serving.__main__ import main

    def bench(kv_dtype: str, requests: int) -> dict:
        with tee_stdout() as printed:
            rc = main([
                "bench", "--model", "gpt2_124m",
                "--ckpt-dir", str(ckpt_dir), "--optimizer", "adamw",
                # gpt2_124m's own position table, which the checkpoint
                # holds (the CLI would size a fresh one from the buckets)
                "--model-overrides", "max_position=1024",
                "--requests", str(requests),
                "--max-new-tokens", str(SERVE_NEW_TOKENS),
                "--kv-dtype", kv_dtype,
                "--output-dir", str(OUT / f"serving_{kv_dtype}"), "--json",
            ])
        text = printed.getvalue()
        row = json.loads(next(line for line in reversed(text.splitlines())
                              if line.startswith("{")))
        check("serving: backend=tpu," in text, "serving CLI reports tpu")
        check(rc == 0 and row["recompiles_after_warmup"] == 0,
              f"kv={kv_dtype}: exit 0, recompiles_after_warmup == 0 "
              f"({row['compiles']} programs compiled in warmup)")
        check(row["completed"] == requests
              and row["tokens"] == requests * SERVE_NEW_TOKENS,
              f"kv={kv_dtype}: {requests} requests answered, "
              f"{row['tokens']} tokens")
        check(row["checkpoint"]["verified"]
              and row["checkpoint"]["step"] == TRAIN_STEPS,
              f"kv={kv_dtype}: served the verified step-{TRAIN_STEPS} "
              "checkpoint")
        # the HLO contract rules were written against CPU HLO text: their
        # verdict on the TPU program is reported, not required
        print(f"  kv={kv_dtype}: serving HLO contracts: "
              f"{row.get('contracts')}", flush=True)
        return row

    bench("fp32", SERVE_REQUESTS)
    row = bench("int8", SERVE_REQUESTS // 2)
    print(f"  int8 page codec: {row['kv_codec']}", flush=True)


def main() -> int:
    t_start = time.perf_counter()
    device = phase_device()
    meter = CompileMeter()
    summary_path = OUT / "summary.json"
    previous = (json.loads(summary_path.read_text())
                if summary_path.exists() else None)
    OUT.mkdir(exist_ok=True)
    for stale in ("train", "ckpt", "serving_fp32", "serving_int8"):
        shutil.rmtree(OUT / stale, ignore_errors=True)

    phases = {}

    def run(name, fn, *args):
        print(f"== phase {name}", flush=True)
        t0, c0 = time.perf_counter(), meter.seconds
        result = fn(*args)
        phases[name] = {
            "seconds": round(time.perf_counter() - t0, 1),
            "compile_seconds": round(meter.seconds - c0, 1)}
        print(f"== phase {name}: {phases[name]['seconds']}s "
              f"({phases[name]['compile_seconds']}s compiling)", flush=True)
        return result

    try:
        run("kernels", phase_kernels)
        ckpt_dir = run("train", phase_train, device["count"])
        run("serve", phase_serve, ckpt_dir)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        shutil.rmtree(OUT / "ckpt", ignore_errors=True)  # ~1.5 GB

    summary = {
        "device": device, "phases": phases,
        "seconds": round(time.perf_counter() - t_start, 1),
        "compile_seconds": round(meter.seconds, 1),
        "compile_cache_hits": meter.hits,
        "compile_cache_misses": meter.misses,
        "previous_compile_seconds": (previous or {}).get("compile_seconds"),
        "claim": None,
    }
    summary_path.write_text(json.dumps(summary) + "\n")
    print(json.dumps(summary), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
