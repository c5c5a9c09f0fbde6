"""Kernel-level microbench: fused weighted-moments path vs naive jnp.

On this CPU container the Pallas kernels run in interpret mode (a
correctness tool, not a perf tool), so the timing comparison here is the
fused *algorithm* (one pass, three moments) against the naive version — the
structural win the TPU kernel encodes.  The VMEM/MXU design constants are
reported as derived metadata for the roofline discussion.

``run_bootstrap`` benchmarks the matrix-free resample loop (in-kernel
counter-based RNG fused into the contraction, via the scan lowering on CPU)
against the materialized-(B, n) weight-matrix path and the naive 3-pass
formulation — plus the bf16-input variant (ROADMAP study: x and w enter the
dots in bf16 with f32 accumulators), quantifying its cv error against the
f32 kernel — and writes the trajectory to BENCH_bootstrap.json so perf is
tracked PR-over-PR.  ``run_kmeans`` does the same for bootstrap-over-
k-means (BENCH_kmeans.json); ``run_quantile`` for the fused Quantile sketch
(kernels/weighted_hist.fused_poisson_hist vs materializing the implicit
weights and scatter-adding per resample), writing BENCH_quantile.json;
``run_stream`` for the double-buffered streaming driver vs the
non-overlapped materialize-then-compute pipeline (BENCH_stream.json).

``--smoke`` (or ``run(smoke=True)``) drives every kernel dispatch path at
tiny shapes with NO timing and NO BENCH_*.json writes — a tier-1 pytest
runs it (tests/test_kernelbench_smoke.py) so dispatch regressions fail in
CI instead of only surfacing in benchmark runs.
"""
import json
import pathlib

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit
from repro.core.bootstrap import fused_resample_states
from repro.core.reduce_api import (KMeansStep, Mean, Quantile,
                                   StatisticGroup, Var)
from repro.kernels.fused_multi import ops as fm_ops
from repro.kernels.kmeans_assign import ops as ka_ops
from repro.kernels.weighted_hist import ops as wh_ops
from repro.kernels.weighted_stats import ops as ws_ops

_ROOT = pathlib.Path(__file__).resolve().parent.parent
_BENCH_JSON = _ROOT / "BENCH_bootstrap.json"
_BENCH_KMEANS_JSON = _ROOT / "BENCH_kmeans.json"
_BENCH_QUANTILE_JSON = _ROOT / "BENCH_quantile.json"
_BENCH_MULTI_JSON = _ROOT / "BENCH_multi.json"
_BENCH_STREAM_JSON = _ROOT / "BENCH_stream.json"
_BENCH_GROUPED_JSON = _ROOT / "BENCH_grouped.json"
_BENCH_FT_JSON = _ROOT / "BENCH_ft.json"
_BENCH_LIVE_JSON = _ROOT / "BENCH_live.json"
_BENCH_DURABLE_JSON = _ROOT / "BENCH_durable.json"


def _timer(smoke: bool):
    """smoke: execute once (so every dispatch path actually runs), report
    0 — the smoke run is a correctness/dispatch gate, not a perf tool."""
    if smoke:
        def _once(fn):
            jax.block_until_ready(fn())
            return 0.0
        return _once
    return lambda fn: timeit(lambda: jax.block_until_ready(fn()))


def _naive(w, x):
    w_tot = jnp.sum(w, axis=1)
    s1 = w @ x
    s2 = w @ (x * x)
    return w_tot, s1, s2


def run(smoke: bool = False) -> None:
    time = _timer(smoke)
    key = jax.random.PRNGKey(7)
    B, n, d = (8, 512, 3) if smoke else (64, 65_536, 8)
    w = jax.random.poisson(key, 1.0, (B, n)).astype(jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (n, d))

    fused = jax.jit(lambda w, x: ws_ops.weighted_moments(w, x,
                                                         backend="jnp"))
    # "naive" = three separate jitted passes over W (models 3 HBM reads of
    # the (B, n) weight matrix; the TPU kernel reads each W tile once)
    n1 = jax.jit(lambda w: jnp.sum(w, axis=1))
    n2 = jax.jit(lambda w, x: w @ x)
    n3 = jax.jit(lambda w, x: w @ (x * x))
    us_f = time(lambda: fused(w, x))
    us_n = time(lambda: (n1(w), n2(w, x), n3(w, x)))
    emit("kernel_weighted_moments_fused", us_f, "")
    emit("kernel_weighted_moments_3pass", us_n,
         f"fused_speedup={us_n / max(us_f, 1e-9):.2f}x;"
         f"w_bytes_read_ratio=3.0")

    # kernel design constants (per EXAMPLE tile): VMEM working set
    bb, bn, bd = 128, 512, 128
    vmem = (bb * bn + bn * bd + 2 * bb * bd + bb) * 4
    intensity = (2 * 2 * bb * bn * bd) / ((bb * bn + bn * bd) * 4)
    emit("kernel_weighted_moments_design", 0.0,
         f"tile_vmem_bytes={vmem};arith_intensity={intensity:.1f}"
         f";mxu_aligned={bb % 128 == 0 and bd % 128 == 0}")

    run_bootstrap(smoke=smoke)
    run_histogram(smoke=smoke)
    run_quantile(smoke=smoke)
    run_kmeans(smoke=smoke)
    run_multi(smoke=smoke)
    run_grouped(smoke=smoke)
    run_stream(smoke=smoke)
    run_ft(smoke=smoke)
    run_live(smoke=smoke)
    run_durable(smoke=smoke)


def _cv(thetas):
    m = jnp.mean(thetas, axis=0)
    return float(jnp.mean(jnp.std(thetas, axis=0) / (jnp.abs(m) + 1e-12)))


def run_bootstrap(smoke: bool = False) -> None:
    """Matrix-free bootstrap: fused-RNG (f32 and bf16-input) vs
    materialized-W vs naive 3-pass.

    The fused-RNG path never builds the (B, n) weight matrix (peak live
    memory O(B·block_n + B·d) on CPU, O(B·d) HBM on TPU); the other two pay
    for both the jax.random.poisson draw of (B, n) and its memory traffic.
    The bf16 variant feeds x/w to the dots in bf16 with f32 accumulators
    (halves X-side HBM/VMEM traffic on TPU) — the emitted cv_rel_err
    quantifies what that costs in bootstrap-accuracy terms.
    """
    time = _timer(smoke)
    B, n, d = (8, 512, 2) if smoke else (256, 1 << 16, 8)
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(jax.random.fold_in(key, 1), (n, d))

    @jax.jit
    def materialized(key, x):
        w = jax.random.poisson(key, 1.0, (B, n)).astype(jnp.float32)
        return jnp.sum(w, axis=1), w @ x, w @ (x * x)

    wgen = jax.jit(
        lambda key: jax.random.poisson(key, 1.0, (B, n)).astype(jnp.float32))
    p1 = jax.jit(lambda w: jnp.sum(w, axis=1))
    p2 = jax.jit(lambda w, x: w @ x)
    p3 = jax.jit(lambda w, x: w @ (x * x))

    def naive():
        w = wgen(key)
        return p1(w), p2(w, x), p3(w, x)

    us_fused = time(lambda: ws_ops.fused_poisson_moments(7, x, B))
    us_bf16 = time(lambda: ws_ops.fused_poisson_moments(
        7, x, B, dtype=jnp.bfloat16))
    us_mat = time(lambda: materialized(key, x))
    us_naive = time(naive)

    # bf16 accuracy study: same implicit weights, different input precision
    # — compare the bootstrap cv of the Mean (the quantity EARL's AES
    # gates on) and the raw moment error.
    wt32, s1_32, s2_32 = ws_ops.fused_poisson_moments(7, x, B)
    wtbf, s1_bf, s2_bf = ws_ops.fused_poisson_moments(7, x, B,
                                                      dtype=jnp.bfloat16)
    cv32 = _cv(s1_32 / wt32[:, None])
    cvbf = _cv(s1_bf / wtbf[:, None])
    cv_rel_err = abs(cvbf - cv32) / max(cv32, 1e-12)
    # scale-normalized moment error (element-wise relative error is
    # meaningless for s1 of zero-mean data, where the true sums sit near 0)
    s1_rel = float(jnp.max(jnp.abs(s1_bf - s1_32))
                   / (jnp.max(jnp.abs(s1_32)) + 1e-9))
    s2_rel = float(jnp.max(jnp.abs(s2_bf - s2_32))
                   / (jnp.max(jnp.abs(s2_32)) + 1e-9))

    speedup_mat = us_mat / max(us_fused, 1e-9)
    speedup_naive = us_naive / max(us_fused, 1e-9)
    emit("bootstrap_fused_rng", us_fused,
         f"B={B};n={n};d={d};weight_matrix_bytes=0")
    emit("bootstrap_fused_rng_bf16", us_bf16,
         f"cv_rel_err={cv_rel_err:.2e};s1_rel_err={s1_rel:.2e};"
         f"s2_rel_err={s2_rel:.2e}")
    emit("bootstrap_materialized_w", us_mat,
         f"fused_speedup={speedup_mat:.2f}x;weight_matrix_bytes={4 * B * n}")
    emit("bootstrap_naive_3pass", us_naive,
         f"fused_speedup={speedup_naive:.2f}x;w_bytes_read_ratio=3.0")

    if smoke:
        return
    _BENCH_JSON.write_text(json.dumps({
        "config": {"B": B, "n": n, "d": d,
                   "backend": jax.default_backend(),
                   "fused_lowering": ("pallas"
                                      if jax.default_backend() == "tpu"
                                      else "scan")},
        "us_per_call": {"fused_rng": us_fused,
                        "fused_rng_bf16": us_bf16,
                        "materialized_w": us_mat,
                        "naive_3pass": us_naive},
        "speedup_fused_vs_materialized": speedup_mat,
        "speedup_fused_vs_naive": speedup_naive,
        "bf16_study": {"cv_f32": cv32, "cv_bf16": cvbf,
                       "cv_rel_err": cv_rel_err,
                       "s1_max_rel_err": s1_rel,
                       "s2_max_rel_err": s2_rel,
                       "x_bytes_ratio_vs_f32": 0.5},
        "peak_weight_bytes": {"fused_rng": 0,
                              "materialized_w": 4 * B * n,
                              "naive_3pass": 4 * B * n},
    }, indent=2) + "\n")


def run_quantile(smoke: bool = False) -> None:
    """Matrix-free bootstrap-over-Quantile: fused histogram sketch vs
    materializing the SAME implicit weights and scatter-adding per resample.

    The fused path (kernels/weighted_hist.fused_poisson_hist, scan lowering
    on CPU) generates the Poisson(1) weights in-pass and bins tile-locally
    — neither the (B, n) weight matrix nor any (n, d, nbins) one-hot
    exists; peak live state is the (B, d, nbins) sketch accumulator.
    """
    time = _timer(smoke)
    B, n, nbins = (8, 512, 64) if smoke else (256, 1 << 16, 2048)
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (n,)) * 2.0 + 8.0
    q = Quantile(0.5, nbins=nbins, lo=0.0, hi=16.0)

    @jax.jit
    def fused(x):
        return wh_ops.fused_poisson_hist(7, x[:, None], q.lo, q.hi,
                                         nbins, B)

    @jax.jit
    def materialized(x):
        w = ws_ops.implicit_weights(7, B, n)
        st0 = q.init_state(1)
        return jax.vmap(lambda wr: q.update(st0, x, wr).counts)(w)

    us_fused = time(lambda: fused(x))
    us_mat = time(lambda: materialized(x))
    speedup = us_mat / max(us_fused, 1e-9)
    emit("quantile_bootstrap_fused", us_fused,
         f"B={B};n={n};nbins={nbins};weight_matrix_bytes=0")
    emit("quantile_bootstrap_materialized", us_mat,
         f"fused_speedup={speedup:.2f}x;weight_matrix_bytes={4 * B * n}")

    if smoke:
        return
    _BENCH_QUANTILE_JSON.write_text(json.dumps({
        "config": {"B": B, "n": n, "d": 1, "nbins": nbins,
                   "backend": jax.default_backend(),
                   "fused_lowering": ("pallas"
                                      if jax.default_backend() == "tpu"
                                      else "scan")},
        "us_per_call": {"fused": us_fused, "materialized": us_mat},
        "speedup_fused_vs_materialized": speedup,
        "peak_intermediate_bytes": {
            "fused": 4 * (B * 512 + B * nbins),   # weight tile + sketch
            "materialized": 4 * B * n,            # implicit weights
        },
    }, indent=2) + "\n")


def run_kmeans(smoke: bool = False) -> None:
    """Bootstrap-over-k-means: fused assignment+accumulate vs materialized.

    The materialized path draws the (B, n) Poisson weight matrix AND builds
    the (B, n, k) weighted one-hot inside the vmapped KMeansStep.update;
    the fused path (kernels/kmeans_assign, scan lowering on CPU) generates
    the weights in-pass and keeps assignment tile-local — peak live state
    O(B·k·d).  A single-state assignment pass is timed too (tiled vs the
    materialized (n, k) distance/one-hot).
    """
    time = _timer(smoke)
    B, n, k, d = (8, 512, 3, 2) if smoke else (64, 1 << 16, 8, 2)
    key = jax.random.PRNGKey(11)
    x = jax.random.normal(key, (n, d))
    cent = jax.random.normal(jax.random.fold_in(key, 1), (k, d)) * 2

    @jax.jit
    def materialized(key, x, cent):
        stat = KMeansStep(cent)
        w = jax.random.poisson(key, 1.0, (B, n)).astype(jnp.float32)
        st = jax.vmap(lambda wr: stat.update(stat.init_state(d), x, wr))(w)
        return st.sums, st.counts, st.inertia

    us_mat = time(lambda: materialized(key, x, cent))
    us_fused = time(lambda: ka_ops.fused_poisson_kmeans(7, x, cent, B))
    speedup = us_mat / max(us_fused, 1e-9)
    emit("kmeans_bootstrap_fused", us_fused,
         f"B={B};n={n};k={k};d={d};weight_matrix_bytes=0;onehot_bytes=0")
    emit("kmeans_bootstrap_materialized", us_mat,
         f"fused_speedup={speedup:.2f}x;"
         f"weight_matrix_bytes={4 * B * n};onehot_bytes={4 * B * n * k}")

    # single-state assignment pass: tiled scan vs materialized (n, k)
    assign_jnp = jax.jit(
        lambda x, cent: ka_ops.kmeans_assign(x, None, cent, backend="jnp"))
    us_a_jnp = time(lambda: assign_jnp(x, cent))
    us_a_scan = time(lambda: ka_ops.kmeans_assign(x, None, cent,
                                                  backend="scan"))
    emit("kmeans_assign_scan", us_a_scan, f"n={n};k={k};d={d}")
    emit("kmeans_assign_materialized", us_a_jnp,
         f"scan_speedup={us_a_jnp / max(us_a_scan, 1e-9):.2f}x;"
         f"nk_bytes={4 * n * k}")

    if smoke:
        return
    _BENCH_KMEANS_JSON.write_text(json.dumps({
        "config": {"B": B, "n": n, "k": k, "d": d,
                   "backend": jax.default_backend(),
                   "fused_lowering": ("pallas"
                                      if jax.default_backend() == "tpu"
                                      else "scan")},
        "us_per_call": {"fused": us_fused,
                        "materialized": us_mat,
                        "assign_scan": us_a_scan,
                        "assign_materialized": us_a_jnp},
        "speedup_fused_vs_materialized": speedup,
        "peak_intermediate_bytes": {
            "fused": 4 * (B * 512 + B * k * d),       # weight tile + states
            "materialized": 4 * B * n * (1 + k),      # weights + one-hot
        },
    }, indent=2) + "\n")


def run_multi(smoke: bool = False) -> None:
    """Single-pass multi-statistic bootstrap (StatisticGroup) vs k
    sequential fused runs of the same statistics.

    The k=3 group (mean + variance + median) pays ONE implicit Poisson(1)
    weight stream and one pass over x — mean and variance additionally
    share one moment accumulator slot — where the sequential baseline
    regenerates an identical-cost threefry stream and re-reads x per
    statistic.  Each sequential statistic is its own jitted dispatch
    (three ``bootstrap`` calls, the pre-group workflow); fusing them into
    one jit would let XLA CSE the duplicate moment pass and misreport the
    baseline.
    """
    time = _timer(smoke)
    B, n, nbins = (8, 512, 64) if smoke else (256, 1 << 16, 2048)
    key = jax.random.PRNGKey(13)
    x2 = (jax.random.normal(key, (n,)) * 2.0 + 8.0)[:, None]
    members = (Mean(), Var(), Quantile(0.5, nbins=nbins, lo=0.0, hi=16.0))
    group = StatisticGroup(members)

    @jax.jit
    def grp(x2):
        return jax.vmap(group.finalize)(
            fused_resample_states(group, 7, x2, B))

    seqs = [jax.jit(lambda x2, m=m: jax.vmap(m.finalize)(
        fused_resample_states(m, 7, x2, B))) for m in members]

    if smoke:
        us_grp = time(lambda: grp(x2))
        us_seq = time(lambda: [f(x2) for f in seqs])
        speedup = us_seq / max(us_grp, 1e-9)
    else:
        # this ratio is an acceptance gate and the container's background
        # load drifts on the timescale of a single run — interleave the
        # two measurements and gate on the median of PER-PAIR ratios, so
        # a load spike hits both sides of each pair instead of one.
        import time as _time
        jax.block_until_ready(grp(x2))
        [jax.block_until_ready(f(x2)) for f in seqs]
        tg, ts = [], []
        for _ in range(7):
            t0 = _time.perf_counter()
            jax.block_until_ready(grp(x2))
            tg.append(_time.perf_counter() - t0)
            t0 = _time.perf_counter()
            [jax.block_until_ready(f(x2)) for f in seqs]
            ts.append(_time.perf_counter() - t0)
        ratios = sorted(b / a for a, b in zip(tg, ts))
        speedup = ratios[len(ratios) // 2]
        us_grp = sorted(tg)[len(tg) // 2] * 1e6
        us_seq = sorted(ts)[len(ts) // 2] * 1e6
    emit("multi_bootstrap_group", us_grp,
         f"B={B};n={n};k={len(members)};slots={len(group.slots)};"
         f"nbins={nbins};weight_streams=1")
    emit("multi_bootstrap_sequential", us_seq,
         f"group_speedup={speedup:.2f}x;weight_streams={len(members)}")

    # shared weights => member thetas identical to their dedicated fused
    # runs (joint CIs); record the invariant alongside the timing.
    tg = grp(x2)
    same = all(bool(jnp.array_equal(tg[i], f(x2)))
               for i, f in enumerate(seqs))
    emit("multi_bootstrap_shared_weights", 0.0, f"member_bitwise={same}")

    if smoke:
        # exercise the Pallas multi-kernel dispatch (interpret mode on CPU)
        jax.block_until_ready(jax.tree_util.tree_leaves(
            fm_ops.fused_poisson_multi(group, 7, x2, B,
                                       backend="pallas_interpret"))[0])
        return
    _BENCH_MULTI_JSON.write_text(json.dumps({
        "config": {"B": B, "n": n, "k": len(members),
                   "slots": len(group.slots), "nbins": nbins,
                   "backend": jax.default_backend(),
                   "fused_lowering": ("pallas"
                                      if jax.default_backend() == "tpu"
                                      else "scan")},
        "us_per_call": {"group": us_grp, "sequential": us_seq},
        "speedup_group_vs_sequential": speedup,
        "member_thetas_bitwise_equal_to_sequential": same,
        "weight_streams": {"group": 1, "sequential": len(members)},
    }, indent=2) + "\n")


def run_grouped(smoke: bool = False) -> None:
    """GROUP BY bootstrap (GroupedStatistic) vs G sequential per-key runs.

    The grouped path pays ONE implicit Poisson(1) weight stream and one
    pass over x, routing each weight tile into G per-key accumulator
    slots by exact 0/1 key masks; the sequential baseline reruns the
    fused kernel per key with ``valid_mask = (key == g)`` — G threefry
    streams of identical cost and G passes over x.  Each per-key run is
    its own jitted dispatch (the pre-GROUP-BY workflow); the PRNG + data
    pass dominate on CPU, so grouped should approach G×/(1 + small
    per-key dot overhead) — the regression gate floors the ratio at 2×
    for G=8 means.
    """
    time = _timer(smoke)
    from repro.core.reduce_api import GroupedStatistic
    B, n, d, G = (8, 512, 2, 4) if smoke else (256, 1 << 16, 4, 8)
    key = jax.random.PRNGKey(19)
    x = jax.random.normal(key, (n, d))
    gid = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, G)
    vals = jnp.concatenate([x, gid[:, None].astype(jnp.float32)], axis=1)
    stat = GroupedStatistic(Mean(), G)
    inner = Mean()

    @jax.jit
    def grouped(vals):
        return jax.vmap(stat.finalize)(
            fused_resample_states(stat, 7, vals, B))

    seqs = [jax.jit(lambda x, g=g: jax.vmap(inner.finalize)(
        fused_resample_states(inner, 7, x, B,
                              valid_mask=(gid == g).astype(jnp.float32))))
        for g in range(G)]

    if smoke:
        us_grp = time(lambda: grouped(vals))
        us_seq = time(lambda: [f(x) for f in seqs])
        speedup = us_seq / max(us_grp, 1e-9)
    else:
        # interleaved paired-ratio discipline (see run_multi): the ratio
        # is an acceptance gate, so each rep times both sides back to
        # back and the gate takes the median of per-pair ratios.
        import time as _time
        jax.block_until_ready(grouped(vals))
        [jax.block_until_ready(f(x)) for f in seqs]
        tg, ts = [], []
        for _ in range(7):
            t0 = _time.perf_counter()
            jax.block_until_ready(grouped(vals))
            tg.append(_time.perf_counter() - t0)
            t0 = _time.perf_counter()
            [jax.block_until_ready(f(x)) for f in seqs]
            ts.append(_time.perf_counter() - t0)
        ratios = sorted(b / a for a, b in zip(tg, ts))
        speedup = ratios[len(ratios) // 2]
        us_grp = sorted(tg)[len(tg) // 2] * 1e6
        us_seq = sorted(ts)[len(ts) // 2] * 1e6
    emit("grouped_bootstrap", us_grp,
         f"B={B};n={n};d={d};G={G};weight_streams=1")
    emit("grouped_bootstrap_sequential", us_seq,
         f"grouped_speedup={speedup:.2f}x;weight_streams={G}")

    # common random numbers => key g's thetas identical to the dedicated
    # per-key fused run under valid_mask=(key==g); record the invariant.
    tgv = grouped(vals)
    same = all(bool(jnp.array_equal(tgv[:, g], f(x)))
               for g, f in enumerate(seqs))
    emit("grouped_bootstrap_per_key", 0.0, f"per_key_bitwise={same}")

    if smoke:
        # exercise the grouped Pallas moments kernel (interpret on CPU)
        jax.block_until_ready(ws_ops.fused_poisson_moments(
            7, x, B, backend="pallas_interpret", group_ids=gid,
            num_groups=G)[0])
        return
    _BENCH_GROUPED_JSON.write_text(json.dumps({
        "config": {"B": B, "n": n, "d": d, "G": G,
                   "backend": jax.default_backend(),
                   "fused_lowering": ("pallas"
                                      if jax.default_backend() == "tpu"
                                      else "scan")},
        "us_per_call": {"grouped": us_grp, "sequential": us_seq},
        "speedup_grouped_vs_sequential": speedup,
        "per_key_thetas_bitwise_equal_to_sequential": same,
        "weight_streams": {"grouped": 1, "sequential": G},
    }, indent=2) + "\n")


def run_stream(smoke: bool = False) -> None:
    """Double-buffered streaming bootstrap over a ShardedStore vs the
    non-overlapped serial transfer+compute pipeline.

    The serial baseline is what the pre-streaming API required: transfer
    EVERYTHING (``read_all`` concat → full f32 decode → one big
    ``device_put``), *then* compute (warm jitted fused chunk scan — jitted
    so the baseline pays transfer+compute, not Python retracing).  The
    streamed path interleaves chunk-sized staging with compute through the
    prefetch queue, so staging stays cache-resident and nothing of size n
    is ever materialized on host or device.

    On this 1-CPU container stage and compute timeshare one core, so the
    win measured here is the avoided full-size materialization passes
    (concat + whole-array decode + whole-array device_put + on-device
    pad/reshape), not thread-level overlap; ``overlap_efficiency``
    (stream wall / max(serial transfer, serial compute)) still reports
    how close the pipeline runs to the ideal-overlap bound — on TPU the
    same driver overlaps host decode with device compute for real.

    The store holds float64 rows so staging pays a per-chunk decode (the
    record-decode cost a real on-disk store has).  Streamed thetas must be
    BITWISE equal to ``bootstrap_chunked`` over ``read_all()`` under the
    same (key, chunk) — recorded as an invariant next to the timing.
    """
    import time as _time

    import numpy as np

    from repro.core import fold
    from repro.core.bootstrap import bootstrap_chunked, seed_from_key
    from repro.core.streaming import bootstrap_streaming
    from repro.data.store import ShardedStore

    B, chunk, nchunks, d = (4, 256, 3, 8) if smoke else (8, 8192, 48, 64)
    n = nchunks * chunk - chunk // 2            # ragged tail
    rng = np.random.default_rng(11)
    store = ShardedStore.from_array(rng.normal(size=(n, d)),
                                    split_size=chunk, interleave=False)
    key = jax.random.PRNGKey(17)
    stat = Mean()
    base_seed = seed_from_key(key)

    @jax.jit
    def _chunked_states(xd):
        nn, dim = xd.shape
        xp = jnp.pad(xd, ((0, (-nn) % chunk), (0, 0)))
        xc = xp.reshape(-1, chunk, dim)

        def body(carry, inp):
            i, xi = inp
            n_valid = jnp.minimum(chunk, nn - i * chunk)
            return fold.fold_rows(stat, *carry, xi, base_seed, i, B,
                                  n_valid=n_valid), None

        return jax.lax.scan(body, fold.empty_carry(stat, B, dim),
                            (jnp.arange(xc.shape[0]), xc))[0]

    def serial():
        t0 = _time.perf_counter()
        xh = np.ascontiguousarray(store.read_all(), np.float32)
        xd = jax.block_until_ready(jax.device_put(xh))
        t1 = _time.perf_counter()
        out = jax.block_until_ready(_chunked_states(xd))
        t2 = _time.perf_counter()
        return out, t1 - t0, t2 - t1

    # warm both sides (compile; first store pass)
    rs = bootstrap_streaming(store, stat, B, key, chunk=chunk)
    serial()

    # streamed thetas == bootstrap_chunked(read_all()) bit for bit: the
    # streaming driver is a transport change, not an estimator change.
    rc = bootstrap_chunked(jnp.asarray(store.read_all(), jnp.float32),
                           stat, B=B, key=key, chunk=chunk,
                           backend="fused_rng")
    bits = bool(np.array_equal(np.asarray(rs.thetas), np.asarray(rc.thetas))
                and np.array_equal(np.asarray(rs.estimate),
                                   np.asarray(rc.estimate)))

    if smoke:
        emit("stream_bootstrap", 0.0,
             f"B={B};chunk={chunk};nchunks={nchunks};d={d}")
        emit("stream_bitwise", 0.0,
             f"thetas_bitwise_equal_to_chunked={bits}")
        return

    # same interleaved paired-ratio discipline as run_multi: the speedup
    # is an acceptance gate, so each rep times both pipelines back to
    # back and the gate takes the median of per-pair ratios.
    t_stream, t_serial, t_xfer, t_comp = [], [], [], []
    for _ in range(7):
        t0 = _time.perf_counter()
        rs = bootstrap_streaming(store, stat, B, key, chunk=chunk)
        t_stream.append(_time.perf_counter() - t0)
        t0 = _time.perf_counter()
        _, xfer, comp = serial()
        t_serial.append(_time.perf_counter() - t0)
        t_xfer.append(xfer)
        t_comp.append(comp)

    ratios = sorted(b / a for a, b in zip(t_stream, t_serial))
    speedup = ratios[len(ratios) // 2]
    med = lambda ts: sorted(ts)[len(ts) // 2]  # noqa: E731
    us_stream = med(t_stream) * 1e6
    us_serial = med(t_serial) * 1e6
    us_xfer = med(t_xfer) * 1e6
    us_comp = med(t_comp) * 1e6
    overlap_eff = us_stream / max(us_xfer, us_comp, 1e-9)

    emit("stream_bootstrap", us_stream,
         f"B={B};chunk={chunk};nchunks={nchunks};d={d};queue_depth=2;"
         f"stage_us={rs.stream.stage_s * 1e6:.0f};"
         f"wait_us={rs.stream.wait_s * 1e6:.0f};"
         f"dispatch_us={rs.stream.dispatch_s * 1e6:.0f};"
         f"drain_us={rs.stream.drain_s * 1e6:.0f}")
    emit("stream_serial_baseline", us_serial,
         f"stream_speedup={speedup:.2f}x;transfer_us={us_xfer:.0f};"
         f"compute_us={us_comp:.0f};overlap_eff={overlap_eff:.2f}")
    emit("stream_bitwise", 0.0,
         f"thetas_bitwise_equal_to_chunked={bits}")

    _BENCH_STREAM_JSON.write_text(json.dumps({
        "config": {"B": B, "chunk": chunk, "nchunks": nchunks, "d": d,
                   "rows": n, "store_dtype": "float64",
                   "queue_depth": 2,
                   "backend": jax.default_backend(),
                   "fused_lowering": ("pallas"
                                      if jax.default_backend() == "tpu"
                                      else "scan")},
        "us_per_call": {"stream": us_stream, "serial": us_serial,
                        "serial_transfer": us_xfer,
                        "serial_compute": us_comp},
        "speedup_stream_vs_serial": speedup,
        "overlap_efficiency": overlap_eff,
        "thetas_bitwise_equal_to_chunked": bits,
        "stream_report": {"stage_s": rs.stream.stage_s,
                          "wait_s": rs.stream.wait_s,
                          "dispatch_s": rs.stream.dispatch_s,
                          "drain_s": rs.stream.drain_s,
                          "n_chunks": rs.stream.n_chunks,
                          "rows": rs.stream.rows},
    }, indent=2) + "\n")


def run_ft(smoke: bool = False) -> None:
    """Crash-safety tax and recovery speed for the streaming bootstrap.

    Three questions, each gated or recorded in BENCH_ft.json:

    * What does checkpointing COST?  A streamed run snapshotting its
      donated carry every 8 chunks vs the plain run — same interleaved
      paired-ratio discipline as run_stream (the ratio is an acceptance
      gate: ``checkpoint_overhead_ratio`` must stay <= 1.10).  The carry
      is O(B·d) states, so the tax is device_get + an async npz write
      every 8 chunks, amortized over 8 chunks of compute.
    * How fast is RECOVERY?  Kill the run at the midpoint checkpoint,
      resume, and time the resumed half-run; the resumed result must be
      BITWISE equal to the uninterrupted run (the ``resumed_bitwise_equal``
      invariant), and the resumed pass re-reads only the unconsumed rows.
    * Does a FAULTY run finish hands-off?  Injected transient IOError +
      one permanently dead split under a degrade policy: the run must
      complete with the loss surfaced in its StreamReport.
    """
    import shutil
    import tempfile
    import time as _time

    import numpy as np

    from repro.checkpoint.manager import CheckpointManager
    from repro.core.streaming import bootstrap_streaming
    from repro.data.store import ShardedStore
    from repro.ft import (FailurePolicy, Fault, FaultyStore, RetryPolicy)

    B, chunk, nchunks, d = (4, 256, 3, 8) if smoke else (8, 8192, 48, 64)
    every = 1 if smoke else 8
    n = nchunks * chunk - chunk // 2            # ragged tail
    rng = np.random.default_rng(23)
    store = ShardedStore.from_array(rng.normal(size=(n, d)),
                                    split_size=chunk, interleave=False)
    key = jax.random.PRNGKey(29)
    stat = Mean()
    root = tempfile.mkdtemp(prefix="earl_bench_ft_")

    class _Die(Exception):
        pass

    class _DyingManager(CheckpointManager):
        def __init__(self, r, die_after, **kw):
            super().__init__(r, **kw)
            self.die_after, self.saves = die_after, 0

        def save(self, *a, **kw):
            super().save(*a, **kw)
            self.saves += 1
            if self.saves >= self.die_after:
                raise _Die()

    def plain():
        return bootstrap_streaming(store, stat, B, key, chunk=chunk)

    def checkpointed(tag):
        # fresh root per rep: every rep pays real (not overwritten-warm)
        # directory creation and npz writes
        return bootstrap_streaming(store, stat, B, key, chunk=chunk,
                                   checkpoint=f"{root}/rep_{tag}",
                                   checkpoint_every=every)

    base = plain()                               # warm both pipelines
    rc = checkpointed("warm")
    bits_ckpt = bool(
        np.array_equal(np.asarray(base.thetas), np.asarray(rc.thetas))
        and np.array_equal(np.asarray(base.estimate),
                           np.asarray(rc.estimate)))

    # -- kill at the midpoint checkpoint, resume, time the recovery ------
    kill_at = max(1, nchunks // 2)
    rroot = f"{root}/resume"
    try:
        bootstrap_streaming(store, stat, B, key, chunk=chunk,
                            checkpoint=_DyingManager(rroot, kill_at,
                                                     async_save=False),
                            checkpoint_every=1)
        raise RuntimeError("dying manager did not die")
    except _Die:
        pass
    store.stats.reset()
    t0 = _time.perf_counter()
    rres = bootstrap_streaming(
        store, stat, B, key, chunk=chunk, resume=True,
        checkpoint=CheckpointManager(rroot, async_save=False))
    resume_s = _time.perf_counter() - t0
    rows_reread = int(store.stats.rows_read)
    bits_resume = bool(
        np.array_equal(np.asarray(base.thetas), np.asarray(rres.thetas))
        and np.array_equal(np.asarray(base.estimate),
                           np.asarray(rres.estimate)))

    # -- injected faults: the run must finish without manual intervention
    fstore = FaultyStore(store, [Fault(split=1, kind="io", attempts=1),
                                 Fault(split=2, kind="io", permanent=True)])
    rdeg = bootstrap_streaming(
        fstore, stat, B, key, chunk=chunk,
        policy=FailurePolicy(retry=RetryPolicy(max_attempts=2,
                                               base_delay=0.0),
                             on_exhausted="degrade"))
    degraded_ok = (rdeg.stream.lost_splits == (2,)
                   and rdeg.stream.faults.io_errors == 3
                   and rdeg.stream.faults.splits_lost == 1)

    if smoke:
        emit("ft_checkpoint_stream", 0.0,
             f"B={B};chunk={chunk};nchunks={nchunks};every={every}")
        emit("ft_resume_bitwise", 0.0,
             f"resumed_bitwise_equal={bits_resume};"
             f"checkpointed_bitwise_equal={bits_ckpt};"
             f"degraded_run_completed={degraded_ok}")
        shutil.rmtree(root, ignore_errors=True)
        return

    # interleaved paired-ratio discipline (see run_multi): the overhead
    # ratio is an acceptance gate (<= 1.10), so each rep times plain and
    # checkpointed back to back and the gate takes the median per-pair.
    t_plain, t_ckpt = [], []
    for i in range(7):
        t0 = _time.perf_counter()
        plain()
        t_plain.append(_time.perf_counter() - t0)
        t0 = _time.perf_counter()
        checkpointed(i)
        t_ckpt.append(_time.perf_counter() - t0)
    ratios = sorted(c / p for p, c in zip(t_plain, t_ckpt))
    overhead = ratios[len(ratios) // 2]
    med = lambda ts: sorted(ts)[len(ts) // 2]  # noqa: E731
    us_plain = med(t_plain) * 1e6
    us_ckpt = med(t_ckpt) * 1e6

    emit("ft_stream_plain", us_plain,
         f"B={B};chunk={chunk};nchunks={nchunks};d={d}")
    emit("ft_stream_checkpointed", us_ckpt,
         f"checkpoint_overhead={overhead:.3f}x;every={every};"
         f"n_checkpoints={rc.stream.n_checkpoints};"
         f"checkpoint_us={rc.stream.checkpoint_s * 1e6:.0f}")
    emit("ft_resume", resume_s * 1e6,
         f"killed_at_chunk={kill_at};rows_reread={rows_reread};"
         f"recovery_vs_full={resume_s / max(med(t_plain), 1e-9):.2f}x;"
         f"resumed_bitwise_equal={bits_resume}")
    emit("ft_degraded", 0.0,
         f"lost_splits={rdeg.stream.lost_splits};"
         f"io_errors={rdeg.stream.faults.io_errors};"
         f"completed={degraded_ok}")

    _BENCH_FT_JSON.write_text(json.dumps({
        "config": {"B": B, "chunk": chunk, "nchunks": nchunks, "d": d,
                   "rows": n, "checkpoint_every": every,
                   "backend": jax.default_backend()},
        "us_per_call": {"stream_plain": us_plain,
                        "stream_checkpointed": us_ckpt,
                        "resume_half_run": resume_s * 1e6},
        "checkpoint_overhead_ratio": overhead,
        "n_checkpoints": rc.stream.n_checkpoints,
        "checkpoint_s": rc.stream.checkpoint_s,
        "checkpointed_bitwise_equal": bits_ckpt,
        "resume_recovery": {"killed_at_chunk": kill_at,
                            "total_chunks": nchunks,
                            "rows_reread": rows_reread,
                            "rows_total": n,
                            "recovery_vs_full_ratio":
                                resume_s / max(med(t_plain), 1e-9)},
        "resumed_bitwise_equal": bits_resume,
        "degraded_run_completed": degraded_ok,
        "degraded_faults": {"io_errors": rdeg.stream.faults.io_errors,
                            "retries": rdeg.stream.faults.retries,
                            "splits_lost":
                                rdeg.stream.faults.splits_lost,
                            "lost_splits": list(rdeg.stream.lost_splits)},
    }, indent=2) + "\n")
    shutil.rmtree(root, ignore_errors=True)


def run_live(smoke: bool = False) -> None:
    """Live ingest: sustained fold throughput, lag recovery, shedding.

    Three questions, each recorded or gated in BENCH_live.json:

    * How fast does a standing ``LiveSession`` DRAIN?  Appends land in an
      ``IngestLog`` and a sliding-window session folds + re-emits a
      report per batch — sustained batches/sec (and rows/sec) over a
      pre-filled backlog is the headline, gated by an absolute floor.
    * How fast does it RECOVER from lag?  Stall the consumer while a
      burst accumulates, then measure the time to drain the burst back
      to a clean watermark — reported relative to the steady-state
      per-batch cost.
    * What does SHEDDING cost/buy?  The same burst drained under a
      ``LagPolicy.shed_backlog`` policy: shed fraction, p_eff, and the
      two bitwise invariants (kill/resume mid-stream equals the
      uninterrupted run; the shed fold equals a dedicated valid_mask
      oracle fold) that make degradation trustworthy.
    """
    import shutil
    import tempfile
    import time as _time

    import numpy as np

    from repro.checkpoint.manager import CheckpointManager
    from repro.core.bootstrap import seed_from_key, offset_seed
    from repro.core.reduce_api import SlidingWindow
    from repro.ft.policy import LagPolicy
    from repro.live import IngestLog, LiveSession

    B, rows, nbatch, d = (4, 64, 6, 4) if smoke else (16, 2048, 64, 8)
    win = SlidingWindow(Var(), 4 * rows, rows)   # 4-pane ring, 1 batch/pane
    key = jax.random.PRNGKey(31)
    rng = np.random.default_rng(41)
    batches = [rng.normal(size=(rows, d)).astype(np.float32)
               for _ in range(nbatch)]
    root = tempfile.mkdtemp(prefix="earl_bench_live_")

    def fill_log():
        log = IngestLog()
        for b in batches:
            log.append(b)
        return log

    # -- sustained drain throughput --------------------------------------
    log = fill_log()
    sess = LiveSession(log, win, B=B, key=key)    # warm the fold jit
    sess.poll()
    reps = 1 if smoke else 5
    times = []
    for _ in range(reps):
        log = fill_log()
        s = LiveSession(log, win, B=B, key=key)
        t0 = _time.perf_counter()
        out = s.poll()
        times.append(_time.perf_counter() - t0)
        assert len(out) == nbatch
    drain_s = sorted(times)[len(times) // 2]
    batches_per_sec = nbatch / drain_s
    us_batch = drain_s / nbatch * 1e6
    emit("live_drain", us_batch,
         f"batches_per_sec={batches_per_sec:.1f};"
         f"rows_per_sec={batches_per_sec * rows:.0f};"
         f"B={B};rows={rows};nbatch={nbatch};panes={win.panes}")

    # -- lag recovery: drain a standing burst back to a clean watermark --
    log = IngestLog()
    s = LiveSession(log, win, B=B, key=key)
    for b in batches[:2]:
        log.append(b)
    s.poll()                                      # steady state...
    burst = 4 if smoke else 16
    for b in batches[2:2 + burst]:
        log.append(b)                             # ...consumer stalled
    t0 = _time.perf_counter()
    out = s.poll()
    recovery_s = _time.perf_counter() - t0
    assert len(out) == burst and s.watermark_seq == 1 + burst
    emit("live_lag_recovery", recovery_s * 1e6,
         f"burst={burst};"
         f"recovery_vs_steady={recovery_s / max(us_batch * 1e-6, 1e-12) / burst:.2f}x")

    # -- shedding under backlog + the two bitwise invariants -------------
    policy = LagPolicy(max_lag_batches=4 * burst, shed_backlog=2,
                       p_shed=0.5, shed_seed=77)
    log = fill_log()
    shed_sess = LiveSession(log, win, B=B, key=key, policy=policy)
    shed_sess.poll()
    shed_rep = shed_sess.report()
    shed_fraction = (shed_sess.counters.shed_rows
                     / max(shed_sess.counters.folded * rows, 1))

    # oracle: re-fold the final window's batches by hand with the same
    # seeded masks handed to the kernels as a dedicated valid_mask
    stat = win.stat
    base_seed = seed_from_key(key)
    states = jax.vmap(lambda _: stat.init_state(d))(jnp.arange(B))
    est = stat.init_state(d)
    o_rows = o_valid = 0
    shed_upto = nbatch - 1 - policy.shed_backlog  # lag at fold of seq q
    for sq in range(nbatch - win.panes, nbatch):
        xb = batches[sq]
        if sq < shed_upto:
            r2 = np.random.default_rng((77, sq))
            m = (r2.random(rows) < policy.p_shed).astype(np.float32)
        else:
            m = np.ones(rows, np.float32)
        est = stat.update(est, xb, m)
        delta = fused_resample_states(
            stat, offset_seed(base_seed, jnp.asarray(sq, jnp.int32)),
            xb, B, valid_mask=m)
        states = jax.vmap(stat.merge)(states, delta)
        o_rows += rows
        o_valid += int(m.sum())
    p_eff = o_valid / o_rows
    o_thetas = stat.correct(jax.vmap(stat.finalize)(states), p_eff)
    o_est = stat.correct(stat.finalize(est), p_eff)
    shed_bitwise = bool(
        np.array_equal(np.asarray(shed_rep.thetas), np.asarray(o_thetas))
        and np.array_equal(np.asarray(shed_rep.estimate),
                           np.asarray(o_est))
        and shed_rep.p_eff == p_eff)

    # kill mid-stream (after the nbatch//2-th fold), resume, compare bits
    clean_log = fill_log()
    clean = LiveSession(clean_log, win, B=B, key=key)
    clean.poll()
    clean_rep = clean.report()

    class _Die(Exception):
        pass

    class _DyingManager(CheckpointManager):
        def __init__(self, r, die_after, **kw):
            kw.setdefault("async_save", False)
            super().__init__(r, **kw)
            self.die_after, self.saves = die_after, 0

        def save(self, *a, **kw):
            super().save(*a, **kw)
            self.saves += 1
            if self.saves >= self.die_after:
                raise _Die()

    log = fill_log()
    rroot = f"{root}/resume"
    try:
        LiveSession(log, win, B=B, key=key,
                    checkpoint=_DyingManager(rroot, max(1, nbatch // 2)),
                    checkpoint_every=1).poll()
        raise RuntimeError("dying manager did not die")
    except _Die:
        pass
    rs = LiveSession(log, win, B=B, key=key, resume=True,
                     checkpoint=CheckpointManager(rroot, async_save=False))
    rs.poll()
    rres = rs.report()
    resumed_bitwise = bool(
        np.array_equal(np.asarray(clean_rep.thetas),
                       np.asarray(rres.thetas))
        and np.array_equal(np.asarray(clean_rep.estimate),
                           np.asarray(rres.estimate)))
    ring_bounded = (rs.panes_live <= rs.memory_bound
                    and shed_sess.panes_live <= shed_sess.memory_bound)
    dedup_exact = (rs.counters.folded == nbatch
                   and clean.counters.folded == nbatch)

    emit("live_shed", 0.0,
         f"shed_fraction={shed_fraction:.3f};p_eff={shed_rep.p_eff:.3f};"
         f"shed_bitwise_equal_to_oracle={shed_bitwise};"
         f"resumed_bitwise_equal={resumed_bitwise}")

    if smoke:
        shutil.rmtree(root, ignore_errors=True)
        return

    _BENCH_LIVE_JSON.write_text(json.dumps({
        "config": {"B": B, "rows_per_batch": rows, "nbatch": nbatch,
                   "d": d, "window_size": win.size, "window_slide":
                   win.slide, "panes": win.panes,
                   "backend": jax.default_backend()},
        "us_per_batch": us_batch,
        "batches_per_sec": batches_per_sec,
        "rows_per_sec": batches_per_sec * rows,
        "lag_recovery": {"burst_batches": burst,
                         "recovery_s": recovery_s,
                         "per_batch_vs_steady_ratio":
                             recovery_s / burst / max(drain_s / nbatch,
                                                      1e-12)},
        "shedding": {"shed_fraction": shed_fraction,
                     "p_eff": shed_rep.p_eff,
                     "shed_batches": shed_sess.counters.shed_batches,
                     "shed_rows": shed_sess.counters.shed_rows},
        "shed_bitwise_equal_to_oracle": shed_bitwise,
        "resumed_bitwise_equal": resumed_bitwise,
        "pane_ring_bounded": ring_bounded,
        "dedup_exactly_once": dedup_exact,
    }, indent=2) + "\n")
    shutil.rmtree(root, ignore_errors=True)


def run_durable(smoke: bool = False) -> None:
    """Durable segment log: append tax per fsync policy, recovery scan
    speed, and the two recovery invariants (BENCH_durable.json).

    The pipeline under test is a realistic ingest producer: per batch it
    GENERATES the rows (the upstream cost every real producer pays),
    assembling each batch from smaller arrival chunks the way a real
    receiver drains a socket, and appends it to the log; the rep ends at
    the durability barrier (``flush``).  The in-memory ``IngestLog``
    runs the identical loop — generation included — so the ratio is the
    durability tax of the whole pipeline, not of a bare ``write()``
    against a bare memcpy.  ``fsync=batch`` (the default) is the
    acceptance gate: <= 1.5x the in-memory pipeline, which the
    write-behind writer earns by interleaving segment writes with
    generation while the sync thread's group ``fdatasync``s — device
    I/O, no GIL — overlap both.

    Recovery is timed as a cold scan of the sealed log (CRC-validating
    every record) and extrapolated to seconds per GB; the invariants
    assert the scan is not just fast but RIGHT: the recovered store is
    bitwise equal to the in-memory log fed the same batches, and a torn
    tail write is truncated to the surviving prefix.
    """
    import os
    import shutil
    import tempfile
    import time as _time

    import numpy as np

    from repro.ft import torn_write
    from repro.live import DurableIngestLog, IngestLog
    from repro.live import segment as _segment

    rows, d, nbatch = (64, 4, 4) if smoke else (131072, 4, 32)
    reps = 1 if smoke else 7
    root = tempfile.mkdtemp(prefix="earl_bench_durable_")

    def gen_batches(seed):
        rng = np.random.default_rng(seed)
        chunk = min(rows, 8192)       # arrival granularity (see docstring)
        return lambda: np.concatenate(
            [rng.standard_normal((chunk, d)).astype(np.float32)
             for _ in range(rows // chunk)])

    def mem_pipeline(seed):
        nxt = gen_batches(seed)
        log = IngestLog()
        for _ in range(nbatch):
            log.append(nxt())
        log.flush()
        return log

    def durable_pipeline(seed, tag, fsync):
        nxt = gen_batches(seed)
        with DurableIngestLog(f"{root}/{tag}", fsync=fsync) as log:
            for _ in range(nbatch):
                log.append(nxt())
            log.flush()
        return log

    # warm both pipelines (allocator, fs metadata, writer-thread startup)
    mem_pipeline(0)
    durable_pipeline(0, "warm", "batch")
    shutil.rmtree(f"{root}/warm", ignore_errors=True)

    # interleaved paired-ratio discipline (see run_multi): the batch-mode
    # tax is an acceptance gate, so each rep times the in-memory and the
    # durable pipeline back to back and the gate takes the median of
    # per-pair ratios.  Fresh directory per rep: every rep pays real
    # segment creation, not overwrite-warm inode reuse.
    taxes, t_mems = {f: [] for f in ("never", "batch", "always")}, []
    for i in range(reps):
        t0 = _time.perf_counter()
        mem_pipeline(i)
        t_mem = _time.perf_counter() - t0
        t_mems.append(t_mem)
        for fsync in taxes:
            tag = f"rep{i}_{fsync}"
            t0 = _time.perf_counter()
            durable_pipeline(i, tag, fsync)
            taxes[fsync].append((_time.perf_counter() - t0) / t_mem)
            # drop this rep's segments before the next timing: letting
            # runs accumulate dirty pages makes later reps pay earlier
            # reps' writeback
            shutil.rmtree(f"{root}/{tag}", ignore_errors=True)
    med = lambda ts: sorted(ts)[len(ts) // 2]  # noqa: E731
    tax = {f: med(taxes[f]) for f in taxes}
    us_mem = med(t_mems) * 1e6
    batch_bytes = rows * d * 4
    mb_s = nbatch * batch_bytes / (med(t_mems) * tax["batch"]) / 1e6

    emit("durable_append_mem_baseline", us_mem,
         f"rows={rows};d={d};nbatch={nbatch};batch_bytes={batch_bytes}")
    for fsync in ("never", "batch", "always"):
        emit(f"durable_append_fsync_{fsync}", us_mem * tax[fsync],
             f"tax={tax[fsync]:.3f}x;mb_per_sec="
             f"{nbatch * batch_bytes / (med(t_mems) * tax[fsync]) / 1e6:.0f}")

    # -- recovery: cold CRC-validating scan, and the invariants ----------
    seed = 101
    oracle = mem_pipeline(seed)
    rroot = f"{root}/recovery"
    durable_pipeline(seed, "recovery", "batch")
    log_bytes = sum(
        os.path.getsize(os.path.join(rroot, _segment.segment_name(i)))
        for i in range(nbatch))
    t_scan = []
    for _ in range(reps):
        t0 = _time.perf_counter()
        rec = DurableIngestLog(rroot)
        t_scan.append(_time.perf_counter() - t0)
        rec.close()
    scan_s = med(t_scan)
    scan_s_per_gb = scan_s / log_bytes * 1e9

    rec = DurableIngestLog(rroot)
    recovery_bitwise = (
        rec.recovery.batches == nbatch
        and all(np.array_equal(np.asarray(rec.store.splits[i]),
                               np.asarray(oracle.store.splits[i]))
                and (rec.store.split_checksum(i)
                     == oracle.store.split_checksum(i))
                for i in range(nbatch)))
    rec.close()

    torn_write(os.path.join(rroot, _segment.segment_name(nbatch - 1)),
               keep_bytes=_segment.HEADER_SIZE + 10)
    rec = DurableIngestLog(rroot)
    torn_ok = (
        rec.recovery.batches == nbatch - 1
        and rec.counters.short_reads == 1
        and all(np.array_equal(np.asarray(rec.store.splits[i]),
                               np.asarray(oracle.store.splits[i]))
                for i in range(nbatch - 1)))
    rec.close()

    emit("durable_recovery_scan", scan_s * 1e6,
         f"log_bytes={log_bytes};s_per_gb={scan_s_per_gb:.2f};"
         f"recovery_bitwise_equal={recovery_bitwise};"
         f"torn_recovery_ok={torn_ok}")

    if smoke:
        shutil.rmtree(root, ignore_errors=True)
        return
    _BENCH_DURABLE_JSON.write_text(json.dumps({
        "config": {"rows_per_batch": rows, "d": d, "nbatch": nbatch,
                   "batch_bytes": batch_bytes, "reps": reps,
                   "backend": jax.default_backend()},
        "us_per_pipeline": {
            "mem": us_mem,
            "fsync_never": us_mem * tax["never"],
            "fsync_batch": us_mem * tax["batch"],
            "fsync_always": us_mem * tax["always"]},
        "fsync_tax_never": tax["never"],
        "fsync_tax_batch": tax["batch"],
        "fsync_tax_always": tax["always"],
        "append_mb_per_sec_batch": mb_s,
        "recovery": {"log_bytes": log_bytes, "scan_s": scan_s,
                     "scan_s_per_gb": scan_s_per_gb},
        "recovery_bitwise_equal": recovery_bitwise,
        "torn_recovery_ok": torn_ok,
    }, indent=2) + "\n")
    shutil.rmtree(root, ignore_errors=True)


def run_histogram(smoke: bool = False) -> None:
    """Quantile sketch update: flattened scatter-add vs one_hot+einsum
    (the old (n, d, nbins) memory blowup)."""
    time = _timer(smoke)
    n, d, nbins = (512, 2, 64) if smoke else (1 << 16, 4, 2048)
    key = jax.random.PRNGKey(3)
    x = jax.random.uniform(key, (n, d))
    w = jnp.ones((n,))
    lo, hi = jnp.zeros((d,)), jnp.ones((d,))

    scatter = jax.jit(lambda x, w: wh_ops.weighted_histogram(
        x, w, lo, hi, nbins, backend="jnp"))

    @jax.jit
    def onehot(x, w):
        idx = jnp.clip((x * nbins).astype(jnp.int32), 0, nbins - 1)
        oh = jax.nn.one_hot(idx, nbins, dtype=jnp.float32)
        return jnp.einsum("n,ndb->db", w, oh)

    us_s = time(lambda: scatter(x, w))
    us_o = time(lambda: onehot(x, w))
    emit("hist_scatter_add", us_s,
         f"n={n};d={d};nbins={nbins};peak_bytes={4 * n * d}")
    emit("hist_onehot_einsum", us_o,
         f"scatter_speedup={us_o / max(us_s, 1e-9):.2f}x"
         f";peak_bytes={4 * n * d * nbins}")
    if smoke:
        # smoke also exercises the Pallas interpret dispatch of the sketch
        jax.block_until_ready(wh_ops.weighted_histogram(
            x, w, lo, hi, nbins, backend="pallas_interpret"))


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, no timing, no BENCH_*.json writes — "
                         "kernel dispatch gate for CI")
    args = ap.parse_args()
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    print("name,us_per_call,derived")
    run(smoke=args.smoke)
