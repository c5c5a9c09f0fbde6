"""Bring-up smoke: EARL's main path once, on one TPU chip.

    python chip_smoke.py             # phases (a)-(e) on one chip
    python chip_smoke.py --chips 4   # the mesh session on four chips only

One process drives the chip; nothing here starts another.  The phases go
through the entry points a user calls, at the paper's tau / pilot p / l
(``configs/earl_analytics.py``), over stores of 2^26 rows:

  (a) prng     hardware-PRNG Poisson(1) tile, 256 x 2^20: mean, variance
               and P(0) of the weights against Poisson(1).
  (b) session  ``EarlSession`` over mean / median / std as one
               ``StatisticGroup``; each answer against the float64 answer
               over the whole store.
  (c) kmeans   ``EarlSession`` over one ``KMeansStep`` (k=5) against the
               float64 Lloyd step over the whole store.
  (d) stream   ``bootstrap_streaming`` of the mean over the whole store.
  (e) lowering ``fused_poisson_moments`` on one 2^20-row chunk, hardware
               PRNG against threefry: same bootstrap standard error.

``--chips 4`` runs only (b) over a 4-device ("data",) mesh and the same
session on one device, and checks each answer lies in the other's CI.

Each session runs under key 0 and must neither fall back to the exact job
nor stop short of its rounds (two for (b), one for (c)): the hardware PRNG
and the data are fixed by their seeds, so a session that does either
marks a change in what the session computes.

For (b)-(e) the compiled fold step must hold the Pallas kernel
(``tpu_custom_call``): a lowering that fell back to a scan would pass
every check on the numbers and never exercise the chip.  Each phase prints
one JSON line with its wall and compile seconds and the rows it folded —
bring-up facts, not benchmark metrics.  Any failed check raises, so the
process exits non-zero; only a run in which every phase passed prints the
final ``{"ok": true, "device": ...}`` line.  Off a TPU the script exits at
once and never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import time

import numpy as np

N_ROWS = 1 << 26            # store rows: far past the quickstart's 2M
CHUNK = 1 << 20             # store split, stream chunk, phase-(e) chunk
SEED = 0                    # data, sampler permutation, session and
                            # stream keys

# (b): tau = 0.01 is an absolute bound on c_v differences, so SSABE's
# phase A only grows B-hat towards ceil(1/tau) = 100 when the pilot's c_v
# is well above tau.  Data with std/mean = 18 gives the median a c_v of
# ~0.25 on the 8192-row pilot; at the paper's sigma = 5% SSABE then asks
# for ~25 pilots' worth of rows, and B-hat * n-hat stays far below N.
MEAN, STD = 1.0, 18.0
# the median's sketch spans mean +- std/2: rows outside are clipped into
# the edge bins, which moves no median, and the bins stay far narrower
# than the median's standard error at the sizes the session reaches.
LO, HI = MEAN - STD / 2, MEAN + STD / 2
BIN_W = (HI - LO) / 2048    # Quantile's default nbins

# (c): well-separated blobs give centroids a c_v of ~0.003 on the pilot;
# a sigma below that makes the session fold more rows than the pilot.
KMEANS_SIGMA = 1e-3


class Recorder:
    """Per-phase wall and XLA compile seconds, printed as JSON lines.
    ``count`` is the number of chips the phases use, not the host's."""

    def __init__(self, jax, count):
        dev = jax.devices()[0]
        self.device = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": count}
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    @contextlib.contextmanager
    def phase(self, name, facts):
        """Time the block; print its facts whether or not its checks pass."""
        c0, t0 = self.compile_s, time.perf_counter()
        passed = False
        try:
            yield
            passed = True
        finally:
            print(json.dumps({"phase": name, "passed": passed,
                              "device": self.device,
                              "wall_s": time.perf_counter() - t0,
                              "compile_s": self.compile_s - c0, **facts},
                             default=float), flush=True)


def _check(ok, what):
    if not ok:
        raise AssertionError(what)


def _kernel_compiled(jitted, *args):
    """Compile ``jitted`` for ``args`` and require a Pallas kernel in it."""
    compiled = jitted.lower(*args).compile()
    _check("tpu_custom_call" in compiled.as_text(),
           f"no Pallas kernel in {getattr(jitted, '__name__', jitted)}")
    return compiled


def _check_answer(name, exact, ci_lo, ci_hi, se_ref, extra=0.0):
    """The float64 exact answer must lie inside the CI widened on each side
    by 4 reference standard errors (plus ``extra``, a sketch's bin width),
    and the CI may be at most 3 times the normal-theory 95% width.

    ``se_ref`` is the float64 standard error of the statistic over a
    uniform sample of the rows the session used, computed from the whole
    store.  The CI comes from SSABE's B-hat resamples, which may be as few
    as 4, so its own width is too noisy to serve as the slack; the width
    bound catches a resample spread far beyond sampling error."""
    exact, se_ref = np.ravel(exact), np.ravel(se_ref)
    lo, hi = np.ravel(ci_lo), np.ravel(ci_hi)
    slack = 4.0 * se_ref + extra
    _check(np.all((exact >= lo - slack) & (exact <= hi + slack)),
           f"{name}: exact {exact} outside CI [{lo}, {hi}] +- {slack}")
    _check(np.all(hi - lo <= 3.0 * 2 * 1.96 * se_ref + extra),
           f"{name}: CI [{lo}, {hi}] wider than 3x its reference "
           f"{2 * 1.96 * se_ref}")


def _fold_step_compiled(stat, B, dim, n, mesh=None):
    """Compile the session's fold step (one delta extend of ``n`` rows)."""
    import jax
    import jax.numpy as jnp

    from repro.core.delta import _pd_extend_jit, poisson_delta_init
    from repro.core.reduce_api import split_params
    pd = poisson_delta_init(stat, B, dim, jax.random.PRNGKey(0),
                            backend="fused_rng", mesh=mesh)
    spec, params = split_params(stat)
    x = jax.ShapeDtypeStruct((n, dim), jnp.float32)
    return _kernel_compiled(_pd_extend_jit, pd.states, pd.est_state, pd.key,
                            0, x, params, spec, B, "fused_rng", mesh)


def phase_prng(rec, B=256, n=CHUNK):
    import jax
    import jax.numpy as jnp

    from repro.kernels.poisson_counts.ops import poisson_counts
    facts = {"weights": B * n}
    with rec.phase("a_prng", facts):
        fn = jax.jit(lambda s: poisson_counts(s, B, n, backend="pallas"))
        w = _kernel_compiled(fn, jnp.int32(SEED))(jnp.int32(SEED))
        stats = jax.jit(lambda w: (jnp.mean(w), jnp.var(w),
                                   jnp.mean((w == 0).astype(jnp.float32)),
                                   jnp.min(w), jnp.max(w),
                                   jnp.all(w == jnp.round(w))))(w)
        mean, var, p0, lo, hi, integral = (float(v) for v in stats)
        facts.update(mean=mean, var=var, p0=p0)
        _check(abs(mean - 1.0) <= 1e-2 and abs(var - 1.0) <= 1e-2,
               f"Poisson(1) weights: mean {mean}, variance {var}")
        _check(abs(p0 - math.exp(-1.0)) <= 1e-2, f"P(w=0) = {p0}")
        _check(integral and lo >= 0.0 and hi <= 10.0,
               f"weights not integers in [0, 10]: min {lo}, max {hi}")


def _numeric_store(n):
    """The (b)/(d) store and its float64 answers: (mean, median, std)."""
    from repro.data import ShardedStore, synthetic_numeric
    data = synthetic_numeric(n, mean=MEAN, std=STD, seed=SEED)
    x = data[:, 0].astype(np.float64)
    exact = (x.mean(), np.median(x), x.std())
    return ShardedStore.from_array(data, CHUNK, interleave=False), exact


def _group_se(sd, n):
    """Standard errors of (mean, median, std) over n normal rows."""
    root_n = math.sqrt(n)
    return (sd / root_n, math.sqrt(math.pi / 2) * sd / root_n,
            sd / math.sqrt(2 * n))


def _session(name, sampler, stat, sigma, min_rounds, mesh=None):
    """One session under key ``SEED``; it must answer early, after at
    least ``min_rounds`` rounds."""
    import jax

    from repro.configs.earl_analytics import CONFIG
    from repro.core import EarlSession
    out = EarlSession(sampler, stat, sigma=sigma, tau=CONFIG.tau,
                      p_pilot=CONFIG.p_pilot, l=CONFIG.l,
                      backend="fused_rng",
                      mesh=mesh).run(jax.random.PRNGKey(SEED))
    _check(not out.fell_back and out.iterations >= min_rounds,
           f"{name}: fell_back {out.fell_back}, {out.iterations} rounds "
           f"(needs {min_rounds})")
    return out


def _group():
    from repro.core import Mean, Quantile, StatisticGroup, Std
    return StatisticGroup((Mean(), Quantile(0.5, lo=LO, hi=HI), Std()))


def _check_group(name, out, exact):
    se = _group_se(exact[2], out.n_used)
    for i, member in enumerate(("mean", "median", "std")):
        rep = out.reports[i]
        _check_answer(f"{name} {member}", exact[i], rep.ci_lo, rep.ci_hi,
                      se[i], BIN_W if member == "median" else 0.0)


def _session_facts(out):
    return {"rows": int(out.n_used), "B": int(out.B),
            "iterations": int(out.iterations), "cv": float(out.cv),
            "result": [np.ravel(np.asarray(r, np.float64)).tolist()
                       for r in (out.result if isinstance(out.result, tuple)
                                 else (out.result,))]}


def phase_session(rec, store, exact):
    from repro.configs.earl_analytics import CONFIG
    from repro.data import PreMapSampler
    facts = {"exact": list(exact)}
    with rec.phase("b_session", facts):
        group = _group()
        sampler = PreMapSampler(store, seed=SEED)
        out = _session("session", sampler, group, CONFIG.sigma, 2)
        facts.update(_session_facts(out))
        _check_group("session", out, exact)
        _fold_step_compiled(group, out.B, 1, out.n_used // 2)


def _kmeans_store(n, k):
    """Blobs, a perturbed start, and the float64 Lloyd step from it over
    the whole store: (store, init, new centroids, their standard errors
    per coordinate over a uniform sample of one row)."""
    from repro.data import ShardedStore, synthetic_clusters
    x, centers = synthetic_clusters(n, k=k, dim=2, seed=SEED)
    init = centers + np.random.default_rng(SEED).normal(
        0.0, 0.5, centers.shape).astype(np.float32)
    c = init.astype(np.float64)
    sums = np.zeros_like(c)
    sq = np.zeros_like(c)
    counts = np.zeros(len(c))
    for s in range(0, n, 1 << 22):
        xs = x[s:s + (1 << 22)].astype(np.float64)
        a = ((xs[:, None, :] - c[None, :, :]) ** 2).sum(-1).argmin(1)
        np.add.at(sums, a, xs)
        np.add.at(sq, a, xs * xs)
        counts += np.bincount(a, minlength=len(c))
    mean = sums / counts[:, None]
    var = sq / counts[:, None] - mean ** 2
    # a cluster's centroid over m sampled rows averages ~m*share of them
    se_one_row = np.sqrt(var / (counts / n)[:, None])
    return ShardedStore.from_array(x, CHUNK, interleave=False), init, \
        mean, se_one_row


def phase_kmeans(rec, n, k):
    import jax.numpy as jnp

    from repro.core import KMeansStep
    from repro.data import PreMapSampler
    store, init, exact, se_one_row = _kmeans_store(n, k)
    facts = {"exact": exact.ravel().tolist()}
    with rec.phase("c_kmeans", facts):
        step = KMeansStep(jnp.asarray(init))
        sampler = PreMapSampler(store, seed=SEED)
        out = _session("kmeans session", sampler, step, KMEANS_SIGMA, 1)
        facts.update(_session_facts(out))
        _check_answer("kmeans centroids", exact, out.ci_lo, out.ci_hi,
                      se_one_row / math.sqrt(out.n_used))
        _fold_step_compiled(step, out.B, 2, out.n_used // 2)


def phase_stream(rec, store, exact, B=256):
    import jax
    import jax.numpy as jnp

    from repro.core import Mean, bootstrap_streaming
    from repro.core.fold import empty_carry
    from repro.core.reduce_api import split_params
    from repro.core.streaming import _stream_chunk_jit
    facts = {"rows": int(store.N), "B": B, "chunk": CHUNK}
    with rec.phase("d_stream", facts):
        stat = Mean()
        spec, params = split_params(stat)
        _kernel_compiled(_stream_chunk_jit, *empty_carry(stat, B, 1),
                         jax.ShapeDtypeStruct((CHUNK, 1), jnp.float32),
                         jax.ShapeDtypeStruct((CHUNK,), jnp.float32),
                         jnp.int32(0), jnp.int32(0), params, spec, B)
        res = bootstrap_streaming(store, stat, B=B,
                                  key=jax.random.PRNGKey(SEED), chunk=CHUNK)
        rep = res.stream
        facts.update(estimate=float(np.ravel(res.estimate)[0]),
                     exact=float(exact[0]), stage_s=rep.stage_s,
                     wait_s=rep.wait_s, dispatch_s=rep.dispatch_s,
                     drain_s=rep.drain_s,
                     n_chunks=rep.n_chunks)
        _check(rep.rows == store.N and res.n == store.N,
               f"stream folded {res.n} of {store.N} rows")
        # every row is in: the estimate is the exact mean up to f32 sums
        # (one chunk left out would move it by ~3e-4), and the CI is the
        # bootstrap's over the whole store
        _check(abs(facts["estimate"] - exact[0]) <= 1e-5 * abs(exact[0]),
               f"stream mean {facts['estimate']} vs exact {exact[0]}")
        _check_answer("stream mean", exact[0], res.report.ci_lo,
                      res.report.ci_hi, _group_se(exact[2], store.N)[0])


def phase_lowerings(rec, store, B=1024):
    """Hardware PRNG and threefry draw identically distributed weights, so
    the bootstrap SE of the mean must agree between them and with the
    normal-theory SE; at B=1024 each SE estimate has ~2% relative noise,
    so 15% is a wide margin."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.weighted_stats.ops import fused_poisson_moments
    x = jnp.asarray(next(store.iter_batches(CHUNK)))
    facts = {"rows": 2 * CHUNK, "B": B}
    with rec.phase("e_lowerings", facts):
        ses = {}
        for backend in ("pallas", "scan"):
            fn = jax.jit(lambda s, x, b=backend: fused_poisson_moments(
                s, x, B, backend=b))
            if backend == "pallas":
                compiled = _kernel_compiled(fn, jnp.int32(SEED), x)
            else:
                compiled = fn.lower(jnp.int32(SEED), x).compile()
            w_tot, s1, _ = compiled(jnp.int32(SEED), x)
            thetas = np.asarray(s1[:, 0] / w_tot, np.float64)
            ses[backend] = float(thetas.std(ddof=1))
        analytic = float(np.std(np.asarray(x, np.float64)) / math.sqrt(CHUNK))
        facts.update(se_pallas=ses["pallas"], se_scan=ses["scan"],
                     se_analytic=analytic)
        for name, ref in (("threefry", ses["scan"]), ("analytic", analytic)):
            ratio = ses["pallas"] / ref
            _check(abs(ratio - 1.0) <= 0.15,
                   f"bootstrap SE: hardware PRNG {ses['pallas']} vs {name} "
                   f"{ref} (ratio {ratio})")


def phase_mesh(rec, store, exact, devices):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs.earl_analytics import CONFIG
    from repro.core import poisson_delta_extend, poisson_delta_init
    from repro.core.reduce_api import MomentState
    from repro.data import PreMapSampler
    mesh = Mesh(np.array(devices[:4]), ("data",))
    facts = {"exact": list(exact)}
    with rec.phase("mesh_session", facts):
        group = _group()
        sampler = PreMapSampler(store, seed=SEED)
        one = _session("one-device session", sampler, group, CONFIG.sigma, 2)
        many = _session("mesh session", sampler, group, CONFIG.sigma, 2,
                        mesh=mesh)
        facts.update(one=_session_facts(one), mesh=_session_facts(many))
        _check_group("one-device session", one, exact)
        _check_group("mesh session", many, exact)
        # the two runs fold nested prefixes of one permutation, so their
        # answers differ by at most the sampling error of the smaller run
        se = _group_se(exact[2], min(one.n_used, many.n_used))
        for i, member in enumerate(("mean", "median", "std")):
            extra = BIN_W if member == "median" else 0.0
            a, b = one.reports[i], many.reports[i]
            _check_answer(f"mesh {member} in one-device CI", many.result[i],
                          a.ci_lo, a.ci_hi, se[i], extra)
            _check_answer(f"one-device {member} in mesh CI", one.result[i],
                          b.ci_lo, b.ci_hi, se[i], extra)

        compiled = _fold_step_compiled(group, many.B, 1, many.n_used // 2,
                                       mesh=mesh)
        _check("all-reduce" in compiled.as_text(),
               "mesh fold step holds no cross-chip all-reduce")
        # one fold step by hand, to see where its rows and states live
        x = jax.device_put(sampler.take(0, CHUNK),
                           NamedSharding(mesh, P("data", None)))
        placement = sorted((s.device.id, s.data.shape[0])
                           for s in x.addressable_shards)
        pd = poisson_delta_init(group, many.B, 1, jax.random.PRNGKey(SEED),
                                backend="fused_rng", mesh=mesh)
        pd = poisson_delta_extend(pd, x)
        state_devs = sorted({d.id for leaf in
                             jax.tree_util.tree_leaves(pd.states)
                             for d in leaf.sharding.device_set})
        # the psum'd Poisson(1) weight total of each resample is ~CHUNK
        # (sd sqrt(CHUNK)); one shard's rows only, or each counted 4 times,
        # lands hundreds of standard errors away
        w_tot = next(np.asarray(s.w, np.float64) for s in pd.states
                     if isinstance(s, MomentState))
        w_se = math.sqrt(CHUNK / w_tot.size)
        facts.update(x_shards=placement, state_devices=state_devs,
                     w_tot_mean=float(w_tot.mean()), w_tot_se=w_se)
        _check(len({d for d, _ in placement}) == 4 and len(state_devs) == 4,
               f"mesh run not on 4 devices: x {placement}, "
               f"states {state_devs}")
        _check(abs(w_tot.mean() - CHUNK) <= 5.0 * w_se,
               f"psum'd weight total {w_tot.mean()} per resample, expected "
               f"{CHUNK} +- {5.0 * w_se}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh session, on four chips")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX reports platform "
              f"{devices[0].platform!r}); this smoke runs on a TPU only",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX reports {len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    rec = Recorder(jax, args.chips)

    store, exact = _numeric_store(N_ROWS)
    if args.chips == 4:
        phase_mesh(rec, store, exact, devices)
    else:
        phase_prng(rec)
        phase_session(rec, store, exact)
        phase_stream(rec, store, exact)
        phase_lowerings(rec, store)
        del store
        from repro.configs.earl_analytics import CONFIG
        phase_kmeans(rec, N_ROWS, CONFIG.kmeans_k)
    for mod in ("benchmarks.roofline", "repro.launch.dryrun"):
        _check(mod not in sys.modules, f"{mod} was imported")
    print(json.dumps({"ok": True, "device": rec.device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
