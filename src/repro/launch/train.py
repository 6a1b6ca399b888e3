"""End-to-end H-SGD training driver.

Runs on whatever devices exist — the host CPU (with --reduced), one TPU chip
(qwen2-0.5b at its published widths: see chip_smoke.py), a four-chip host
with --backend mesh: builds the model from --arch, an H-SGD topology
(--workers/--groups/--G/--I, optionally --levels for multi-level), the
synthetic token pipeline, and trains with periodic checkpointing +
divergence telemetry.  Compiled programs persist across runs in
$JAX_COMPILATION_CACHE_DIR, else in <repo>/.jax_cache
(repro.launch.cache).

Execution goes through the schedule-compiled round executor (``run_rounds``):
each pure-local block is one fused dispatch, with the schedule additionally
cut at the telemetry cadence so checkpoints/divergences land exactly on their
steps.  ``--backend`` picks the executor: ``sim`` (default; vmap over the
worker axis on one device) or ``mesh`` (shard_map over a hierarchy-shaped
device mesh — needs prod(level sizes) devices; sync events lower to
named-axis all-reduces).

``--runtime`` prices the schedule in simulated seconds (straggler clocks,
per-level links, optional ``--deadline`` elastic participation —
repro.runtime); telemetry then carries sim_time_s / sim_sync_s and the run
ends with a runtime breakdown + planner constants fitted from the trace.

``--probes`` turns on the in-graph observability layer (repro.obs): the
per-level parameter divergences are measured ON device at every sync event
and drained in bulk — no host gradient recompute, no schedule cut — and
``--trace out.json`` exports the run as Perfetto/Chrome-trace JSON.

``--population`` switches to the sampled-participation regime
(repro.population): the topology's n workers become the k *active slots* of
a declared virtual-client population (cells per level, ``C1xC2x...``), each
sampling round (one global period G) draws fresh clients hierarchically,
and results fold back into a server model — so ``--steps`` must be a
multiple of G and telemetry becomes one record per round.

Flags are grouped per subsystem (``--help`` shows the groups); every
subsystem group builds one section of the engine's ``EngineConfig``, which
is echoed verbatim as the run's JSONL header line.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \
      --workers 8 --groups 2 --G 8 --I 2 --steps 60 --batch 4 --seq 64 \
      --runtime 0.004,0.005:1e9,0.0003:1e10 --straggler lognormal:0.8 \
      --deadline 0.004
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \
      --workers 8 --groups 2 --G 8 --I 2 --steps 64 --batch 4 --seq 64 \
      --population 1000x1000 --sample-k 8 --sample-seed 7
"""
from __future__ import annotations

import argparse
import json
import math
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import restore, save
from repro.comms import Comms
from repro.configs import get_config, reduced
from repro.core import (EngineConfig, HSGD, HierarchySpec, all_divergences,
                        contiguous, make_topology, per_worker_grads)
from repro.data import TokenStream
from repro.launch.cache import enable_compile_cache
from repro.models import build_model
from repro.optim import cosine, momentum, sgd


def build_argparser():
    """Flags grouped per subsystem; each subsystem group feeds one section
    of the engine's :class:`~repro.core.EngineConfig` (echoed as the JSONL
    header's ``config`` line)."""
    ap = argparse.ArgumentParser(
        description="H-SGD training driver (repro.launch.train)")

    g = ap.add_argument_group("model")
    g.add_argument("--arch", default="qwen2-0.5b")
    g.add_argument("--reduced", action="store_true",
                   help="CPU-scale same-family variant")

    g = ap.add_argument_group(
        "topology", "hierarchy shape + the aggregation rule at sync events")
    g.add_argument("--workers", type=int, default=8)
    g.add_argument("--groups", type=int, default=2)
    g.add_argument("--G", type=int, default=8)
    g.add_argument("--I", type=int, default=2)
    g.add_argument("--levels", type=str, default="",
                   help="multi-level spec 'N1,N2,..:P1,P2,..' (overrides "
                        "--workers/--groups/--G/--I)")
    g.add_argument("--aggregator", default="mean",
                   choices=["mean", "compressed", "sign"],
                   help="aggregation rule applied at every sync event")
    g.add_argument("--sync-dtype", default=None,
                   help="aggregation payload dtype override (bfloat16 "
                        "halves sync bytes; alone it implies --aggregator "
                        "compressed)")

    g = ap.add_argument_group(
        "training", "optimizer, schedule length, data shape, executor")
    g.add_argument("--backend", default="sim", choices=["sim", "mesh"],
                   help="executor (EngineConfig.executor): 'sim' "
                        "(single-device vmap) or 'mesh' (shard_map; one "
                        "device per worker, sync events lower to "
                        "named-axis all-reduces)")
    g.add_argument("--steps", type=int, default=50)
    g.add_argument("--batch", type=int, default=4, help="per-worker batch")
    g.add_argument("--seq", type=int, default=64)
    g.add_argument("--lr", type=float, default=3e-3)
    g.add_argument("--optimizer", default="sgd", choices=["sgd", "momentum"])
    g.add_argument("--seed", type=int, default=0)

    g = ap.add_argument_group(
        "comms", "communication plan (EngineConfig.comms)")
    g.add_argument("--comms", default=None,
                   choices=["identity", "int8", "sign", "topk"],
                   help="fuse syncs into flat per-dtype buffers and ship "
                        "them through this codec (repro.comms); adds "
                        "per-level wire accounting to the telemetry.  "
                        "Default: off (bitwise-identical leaf-wise path)")
    g.add_argument("--comms-block", type=int, default=0,
                   help="codec block size override (int8/sign)")
    g.add_argument("--comms-rate", type=float, default=0.0,
                   help="top-k sparsification rate override (topk)")

    g = ap.add_argument_group(
        "runtime", "simulated-time heterogeneity (EngineConfig.runtime)")
    g.add_argument("--runtime", default=None,
                   help="simulated-time model 'COMPUTE[,LAT:BW,...]': "
                        "seconds per local step, then one latency:bandwidth"
                        " pair per hierarchy level outermost-first "
                        "(default links: a 10x-per-tier datacenter ladder)."
                        "  Adds sim_time_s / per-level sim_sync_s to the "
                        "telemetry and a final runtime report; sync cost "
                        "is priced from the comms payload bytes, so "
                        "--comms codecs visibly buy simulated time.  "
                        "Example: --runtime 0.004,0.005:1e9,0.0003:1e10")
    g.add_argument("--straggler", default=None,
                   help="heterogeneity regime 'name[:params]': "
                        "fixed[:frac:factor] | lognormal[:sigma] | "
                        "bursty[:p_enter:p_exit:factor] (needs --runtime)")
    g.add_argument("--deadline", default=None,
                   help="deadline-elastic participation: slack seconds "
                        "('2.0') or per-level 'L1:2.0,L2:0.5' — workers "
                        "missing a sync's deadline are dropped from that "
                        "event only, keeping their params and comms "
                        "residuals (needs --runtime; works on both "
                        "backends)")
    g.add_argument("--runtime-seed", type=int, default=0,
                   help="straggler sampler seed (draws are pure in "
                        "(seed, step): policies compare on identical "
                        "compute times)")

    g = ap.add_argument_group(
        "population",
        "sampled participation from a virtual-client population "
        "(EngineConfig.population; repro.population)")
    g.add_argument("--population", default="",
                   help="declare a virtual-client population as per-level "
                        "cell fanouts 'C1xC2x...' (e.g. 1000x1000 = 10^6 "
                        "clients behind a two-level topology); each "
                        "sampling round (one global period G) draws the "
                        "topology's n clients hierarchically and folds the "
                        "round back into a server model, so --steps must "
                        "be a multiple of G")
    g.add_argument("--sample-k", type=int, default=0,
                   help="expected active clients per round; cross-checked "
                        "against the topology's n (the draw always fills "
                        "exactly n slots)")
    g.add_argument("--sample-seed", type=int, default=0,
                   help="population sampler namespace: draws are pure in "
                        "(sample-seed, round)")

    g = ap.add_argument_group(
        "observability",
        "telemetry, probes, tracing, audits (EngineConfig.metrics)")
    g.add_argument("--audit", action="store_true",
                   help="print the repro.analysis collective audit of the "
                        "lowered sync plan (per-event sync ops, wire "
                        "dtypes, payload bytes, lint findings) before "
                        "training starts")
    g.add_argument("--probes", action="store_true",
                   help="in-graph observability (repro.obs): carry the "
                        "on-device divergence probe through training — "
                        "per-level parameter divergences at every sync "
                        "event (div_global/div_up_Lℓ/div_down_Lℓ in the "
                        "JSONL) plus a per-step grad_norm channel, drained "
                        "in one transfer at telemetry boundaries.  "
                        "--divergence-every is then satisfied by the "
                        "probe values (no host gradient recompute, no "
                        "schedule cut)")
    g.add_argument("--trace", default="",
                   help="export the run as Chrome-trace-event/Perfetto "
                        "JSON to this path (open in ui.perfetto.dev): "
                        "per-worker compute/wait spans and per-level sync "
                        "spans with --runtime, step-index spans without; "
                        "probe divergences ride along as counter tracks "
                        "with --probes")
    g.add_argument("--log-every", type=int, default=10)
    g.add_argument("--divergence-every", type=int, default=0)

    g = ap.add_argument_group("io", "checkpointing and output")
    g.add_argument("--ckpt-dir", default="")
    g.add_argument("--ckpt-every", type=int, default=0)
    g.add_argument("--out", default="")
    return ap


def make_runtime_model(args, num_levels: int):
    """--runtime 'COMPUTE[,LAT:BW,...]' (+ --straggler/--deadline/
    --runtime-seed) -> RuntimeModel, or None with the flag unset."""
    if not args.runtime:
        return None
    from repro.runtime import LinkModel, RuntimeModel
    parts = [p for p in args.runtime.split(",") if p]
    links = None
    if len(parts) > 1:
        if len(parts) - 1 != num_levels:
            raise SystemExit(
                f"--runtime: got {len(parts) - 1} LAT:BW pairs for a "
                f"{num_levels}-level hierarchy (need one per level, "
                f"outermost first)")
        links = tuple(LinkModel(float(lat), float(bw))
                      for lat, bw in (p.split(":") for p in parts[1:]))
    return RuntimeModel(compute_s=float(parts[0]), links=links,
                        straggler=args.straggler, policy=args.deadline,
                        seed=args.runtime_seed)


def make_spec(args) -> HierarchySpec:
    if args.levels:
        sizes, periods = args.levels.split(":")
        return HierarchySpec(tuple(int(x) for x in sizes.split(",")),
                             tuple(int(x) for x in periods.split(",")))
    assert args.workers % args.groups == 0
    return HierarchySpec((args.groups, args.workers // args.groups),
                         (args.G, args.I))


def _run_sampled(args, ap, eng, model, cfg, spec):
    """Population-mode training loop: one sampling round per global period,
    virtual clients' token streams keyed by client id (pure in
    ``(seed, client_id, t)``; empty slots get the reserved stream 0)."""
    from repro.data.synthetic import synth_lm_batch
    G = spec.periods[0]
    server = eng.init_server(jax.random.PRNGKey(args.seed), model.init)
    if args.audit:
        popeng = eng.population_engine()
        print(popeng.audit(server,
                           config=f"{args.backend}/{args.arch}/pop").summary())

    def batch_fn(client_ids, t):
        bs = [synth_lm_batch(args.seed, t, args.batch, args.seq,
                             cfg.vocab_size, worker=int(c) + 1)
              for c in client_ids]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *bs)

    t0 = time.time()
    server, hist = eng.run_sampled(server, batch_fn, args.steps // G)
    elapsed = round(time.time() - t0, 2)
    log_rounds = max(1, args.log_every // G)
    history = []
    for rec in hist:
        if rec["round"] % log_rounds and rec["t"] != args.steps:
            continue
        out = {"step": rec["t"], "round": rec["round"], "loss": rec["ce"],
               "elapsed_s": elapsed, "participation": rec["participation"]}
        for key in ("sim_time_s", "dropped", "wire_bytes"):
            if key in rec:
                out[key] = rec[key]
        history.append(out)
        print(json.dumps(out))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(history, f, indent=1)
    return history


def main(argv=None):
    ap = build_argparser()
    args = ap.parse_args(argv)
    # fail loudly on codec knobs that would otherwise be silently ignored
    if args.comms_block and args.comms not in ("int8", "sign"):
        ap.error(f"--comms-block only applies to --comms int8|sign "
                 f"(got --comms {args.comms})")
    if args.comms_rate and args.comms != "topk":
        ap.error(f"--comms-rate only applies to --comms topk "
                 f"(got --comms {args.comms})")
    if (args.straggler or args.deadline) and not args.runtime:
        ap.error("--straggler/--deadline need --runtime (the simulated "
                 "clock they perturb)")
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = build_model(cfg)
    spec = make_spec(args)
    n = spec.n_workers

    population = None
    if args.population:
        from repro.population import Population
        try:
            cells = tuple(int(c) for c in
                          args.population.lower().replace("x", ",").split(",")
                          if c)
        except ValueError:
            ap.error(f"--population must be per-level cell fanouts like "
                     f"1000x1000 (got {args.population!r})")
        if len(cells) != spec.num_levels:
            ap.error(f"--population {args.population}: {len(cells)} cell "
                     f"fanouts for a {spec.num_levels}-level hierarchy "
                     f"(need one per level)")
        if args.sample_k and args.sample_k != n:
            ap.error(f"--sample-k {args.sample_k} != topology n={n}: the "
                     f"draw fills exactly one client per engine slot, so k "
                     f"is the topology's n (adjust --workers/--levels)")
        if args.steps % spec.periods[0] != 0:
            ap.error(f"--population: --steps {args.steps} must be a "
                     f"multiple of the global period G={spec.periods[0]} "
                     f"(one sampling round per global period)")
        for val, name in ((args.ckpt_dir, "--ckpt-dir"),
                          (args.trace, "--trace"),
                          (args.divergence_every, "--divergence-every")):
            if val:
                ap.error(f"{name} is not supported in population mode")
        population = Population(cells, seed=args.sample_seed)
    elif args.sample_k or args.sample_seed:
        ap.error("--sample-k/--sample-seed need --population")

    lr = cosine(args.lr, args.steps, warmup_steps=min(10, args.steps // 10))
    opt = sgd(lr) if args.optimizer == "sgd" else momentum(lr)
    topo = make_topology(
        "uniform", spec=spec, sync_dtype=args.sync_dtype,
        aggregator=None if args.aggregator == "mean" else args.aggregator)
    comms = None
    if args.comms:
        kw = {}
        if args.comms_block:
            kw["block"] = args.comms_block
        if args.comms_rate:
            kw["rate"] = args.comms_rate
        comms = Comms(args.comms, **kw)
    runtime = make_runtime_model(args, spec.num_levels)
    engine_config = EngineConfig(executor=args.backend, comms=comms,
                                 runtime=runtime,
                                 metrics="on" if args.probes else None,
                                 population=population)
    eng = HSGD(model.loss, opt, topo, engine_config)
    from repro.obs import SCHEMA_VERSION
    # JSONL header: the full engine configuration, round-trippable, and
    # the local step's update path (HSGD.update_path)
    print(json.dumps({"schema_version": SCHEMA_VERSION,
                      "backend": args.backend, "probes": args.probes,
                      "update": eng.update_path,
                      "config": engine_config.describe()}))

    if population is not None:
        return _run_sampled(args, ap, eng, model, cfg, spec)

    state = eng.init(jax.random.PRNGKey(args.seed), model.init)
    if args.audit:
        # sync-subprogram audit only (no batch_fn): fast, and enough for
        # the per-event sync-op/dtype/byte summary + R1/R2/R5 lints
        print(eng.audit(state, config=f"{args.backend}/{args.arch}").summary())
    if comms is not None:
        # static per-level wire accounting: what each sync event moves
        print(json.dumps({"wire": eng.wire_stats(state).summary(args.steps)}))

    stream = TokenStream(seed=args.seed, batch=args.batch, seq_len=args.seq,
                         vocab=cfg.vocab_size, n_workers=n)

    start = 0
    if args.ckpt_dir:
        try:
            start, tree = restore(args.ckpt_dir, {
                "params": state.params, "opt": state.opt_state})
            # codec residuals are not checkpointed: resume restarts error
            # feedback from the fresh (zero) state
            state = eng.executor.place(state.__class__(
                tree["params"], tree["opt"], jnp.asarray(start, jnp.int32),
                state.comms, state.metrics))
            print(f"resumed from step {start}")
        except AssertionError:
            pass

    # telemetry cadence: the round schedule is cut at the gcd of the
    # intervals that need exact-step STATE (checkpoints, divergences), so
    # those land on round boundaries.  Logging reads the per-step history
    # and needs no cut — including it here would degenerate coprime
    # cadences to gcd 1, i.e. per-step dispatch.
    ckpt_every = args.ckpt_every if args.ckpt_dir else 0
    # with --probes the in-graph probe supplies divergences at every sync
    # step (drained in one bulk transfer), so --divergence-every needs
    # neither the host gradient recompute nor a schedule cut
    div_every = 0 if args.probes else args.divergence_every
    intervals = [v for v in (div_every, ckpt_every) if v]
    eval_every = math.gcd(*intervals) if intervals else 0
    # per-level divergence groupings come from the topology (a >2-level
    # schedule reports every internal level, not just level 1)
    groupings = topo.level_groupings() or {1: contiguous(n, 1)}
    t0 = time.time()

    def telemetry(st, t):
        step = t + 1
        rec = {"elapsed_s": round(time.time() - t0, 2)}
        if div_every and step % div_every == 0:
            g = per_worker_grads(model.loss, eng.mean_params(st),
                                 stream(10_000_000 + t))
            rec["divergence"] = {f"L{lvl}": all_divergences(g, gr)
                                 for lvl, gr in groupings.items()}
        if ckpt_every and step % ckpt_every == 0:
            save(args.ckpt_dir, step,
                 {"params": st.params, "opt": st.opt_state})
        return rec

    recorder = None
    if args.trace:
        from repro.obs import TraceRecorder
        recorder = TraceRecorder()
    state, step_hist = eng.run_rounds(
        state, stream, args.steps - start,
        eval_every=eval_every, eval_fn=telemetry, trace=recorder)

    # un-hooked steps get the elapsed_s of the NEXT measured boundary (the
    # telemetry point whose rounds covered them): an upper bound, and
    # monotonic — a plain end-of-run fallback would make earlier records
    # report larger elapsed than later ones
    nxt = round(time.time() - t0, 2)
    for srec in reversed(step_hist):
        nxt = srec.setdefault("elapsed_s", nxt)
    history = []
    wire_cum = 0
    if args.probes:
        from repro.obs import validate_record
    for srec in step_hist:
        step = srec["t"]
        wire_cum += srec.get("wire_bytes", 0)
        # record log-cadence steps, the final step, and every step that
        # carries divergence telemetry — host oracle or in-graph probe
        # (their cadences may not align with --log-every)
        if step % args.log_every == 0 or step == args.steps \
                or "divergence" in srec or "div_global" in srec:
            rec = {"step": step,
                   "loss": srec["ce"],
                   "lvl": spec.sync_level(step - 1),
                   "elapsed_s": srec["elapsed_s"]}
            if "grad_norm" in srec:
                rec["grad_norm"] = srec["grad_norm"]
            if comms is not None:
                rec["wire_cum_bytes"] = wire_cum
            if "sim_time_s" in srec:
                rec["sim_time_s"] = srec["sim_time_s"]
                rec["sim_sync_s"] = srec["sim_sync_s"]
            if "dropped" in srec:
                rec["dropped"] = srec["dropped"]
            rec.update({k: v for k, v in srec.items()
                        if k.startswith("div_")})
            if "divergence" in srec:
                rec["divergence"] = srec["divergence"]
            if args.probes:
                # the launcher's record is fully registered on the metrics
                # bus: lint strictly (None lvl = between syncs, skipped)
                errs = validate_record(
                    {k: v for k, v in rec.items() if v is not None},
                    strict=True)
                if errs:
                    raise SystemExit("metrics-bus violations: "
                                     + "; ".join(errs))
            history.append(rec)
            print(json.dumps(rec))
    if recorder is not None:
        from repro.obs import validate_trace
        assert not validate_trace(recorder), validate_trace(recorder)
        recorder.save(args.trace)
        print(json.dumps({"trace": args.trace,
                          "trace_events": len(recorder.events)}))
    if runtime is not None:
        # where the simulated time went (makespan, waits, per-level links,
        # drop counts) + the fitted planner constants, closing the loop
        # simulate -> fit -> enumerate_plans
        from repro.core import CommModel
        fit = CommModel.fit_from_trace(step_hist, topo)
        print(json.dumps({"runtime": eng.runtime_report(),
                          "fitted_comm_model": {
                              "compute_s": round(fit.compute_s, 9),
                              "local_round_s": round(fit.local_round_s, 9),
                              "global_round_s": round(fit.global_round_s, 9),
                          }}))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(history, f, indent=1)
    return history


if __name__ == "__main__":
    main()
