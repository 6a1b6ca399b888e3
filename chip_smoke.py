"""Bring-up check: H-SGD training of qwen2-0.5b on a TPU, end to end.

    python chip_smoke.py             # one chip: phases 1-3
    python chip_smoke.py --chips 4   # four chips: the mesh phase only

Phases (one process; every phase that fails fails the script):

1. ``repro.launch.train.main`` trains qwen2-0.5b at its published widths and
   full depth (4 workers in 2 groups, G=4, I=2, 8 steps, per-worker batch
   1 x 256, SGD) on the sim executor; every loss must be finite.  Then the
   same library path (``HSGD``, ``make_topology``, ``run_rounds``) checks the
   post-sync invariants: after a level-2 sync the workers of a group hold
   bitwise-equal params and the two groups differ; after a level-1 sync all
   four are bitwise equal.
2. A reduced f32 qwen2 runs the same H-SGD schedule twice in this process,
   on the TPU and on the host CPU device, at ``highest`` matmul precision;
   per-step losses and final params must agree within the stated bounds.
3. The codec kernels run compiled (``interpret=False``) at the real bucket
   width L = 494,032,768 (rows 1 and 4) and are compared with their
   ``repro.kernels.ref`` oracles; the top-k decode-reduce is compared with
   its oracle evaluated on the host CPU device.

``--chips 4`` runs ``--backend mesh`` (one full-width worker per chip, a 2x2
hierarchy) with comms off and with ``--comms int8``, checks that state is
spread over all four chips, the same post-sync invariants, and agreement
with the same run under ``MeshExecutor(exact=True)`` (the bitwise replay of
the sim reduce).

Wall times printed on the way are bring-up observations, not benchmark
numbers.  The last line of standard output is one JSON object naming the
device.  Without a TPU, or without the repo's ``src/`` next to this file,
the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import math
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

SRC = Path(__file__).resolve().parent / "src"
ARCH = "qwen2-0.5b"
L_BUCKET = 494_032_768  # qwen2-0.5b params per worker: the fused bucket width
HIER = dict(workers=4, groups=2, G=4, I=2)
LR = 3e-3


class SmokeFailure(SystemExit):
    def __init__(self, msg: str):
        super().__init__(f"chip_smoke: FAILED: {msg}")


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(**rec) -> None:
    print(json.dumps(rec), flush=True)


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------
class CompileLog:
    """Backend compile durations (seconds) reported by JAX, per phase."""

    def __init__(self):
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append(duration)

    def take(self, min_s: float = 1.0):
        out = [round(d, 2) for d in self.events if d >= min_s]
        self.events.clear()
        return out


def memory(devices):
    return [{k: d.memory_stats().get(k) for k in
             ("bytes_in_use", "peak_bytes_in_use")} for d in devices]


def train_argv(steps: int, *extra: str):
    return ["--arch", ARCH, "--workers", str(HIER["workers"]),
            "--groups", str(HIER["groups"]), "--G", str(HIER["G"]),
            "--I", str(HIER["I"]), "--steps", str(steps), "--batch", "1",
            "--seq", "256", "--optimizer", "sgd", "--lr", str(LR),
            "--log-every", "1", *extra]


def make_engine(cfg, steps: int, executor, comms=None):
    """The engine ``train.main`` builds for ``train_argv(steps)``."""
    from repro.comms import Comms
    from repro.core import HSGD, EngineConfig, HierarchySpec, make_topology
    from repro.models import build_model
    from repro.optim import cosine, sgd
    model = build_model(cfg)
    spec = HierarchySpec((HIER["groups"], HIER["workers"] // HIER["groups"]),
                         (HIER["G"], HIER["I"]))
    opt = sgd(cosine(LR, steps, warmup_steps=min(10, steps // 10)))
    eng = HSGD(model.loss, opt, make_topology("uniform", spec=spec),
               EngineConfig(executor=executor,
                            comms=Comms(comms) if comms else None))
    return eng, model


@jax.jit
def _worker_pairs(tree):
    uint = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}
    leaves = [jax.lax.bitcast_convert_type(x, uint[x.dtype.itemsize])
              for x in jax.tree.leaves(tree)]
    n = leaves[0].shape[0]
    return jnp.stack([jnp.stack([
        jnp.all(jnp.stack([jnp.array_equal(x[i], x[j]) for x in leaves]))
        for j in range(n)]) for i in range(n)])


def worker_equality(params):
    """(n, n) bool: workers i and j hold bitwise-identical params."""
    sharding = jax.tree.leaves(params)[0].sharding
    if len(sharding.device_set) > 1:  # mesh: one worker per chip
        from jax.sharding import NamedSharding, PartitionSpec as P
        params = jax.device_put(params, NamedSharding(sharding.mesh, P()))
    return np.asarray(jax.device_get(_worker_pairs(params)))


def check_sync_invariants(eng, model, seed: int, tag: str, on_init=None):
    """Run I steps (-> level-2 sync) then G - I more (-> level-1 sync) and
    check the post-sync parameter invariants; returns (state, losses).
    ``on_init(state)`` inspects the freshly placed state first."""
    from repro.data import TokenStream
    n, per = HIER["workers"], HIER["workers"] // HIER["groups"]
    group = np.arange(n) // per
    same_group = group[:, None] == group[None, :]
    stream = TokenStream(seed=seed, batch=1, seq_len=256,
                         vocab=model.cfg.vocab_size, n_workers=n)
    state = eng.init(jax.random.PRNGKey(seed), model.init)
    if on_init is not None:
        on_init(state)
    state, h1 = eng.run_rounds(state, stream, HIER["I"])
    eq = worker_equality(state.params)
    check(eq[same_group].all(),
          f"{tag}: after the level-2 sync a group's workers differ: {eq}")
    check(not eq[~same_group].any(),
          f"{tag}: after the level-2 sync two groups hold equal params: {eq}")
    state, h2 = eng.run_rounds(state, stream, HIER["G"] - HIER["I"])
    eq2 = worker_equality(state.params)
    check(eq2.all(), f"{tag}: after the level-1 sync workers differ: {eq2}")
    losses = [r["ce"] for r in h1 + h2]
    check(all(math.isfinite(v) for v in losses), f"{tag}: losses {losses}")
    log(phase=tag, step=int(state.step), losses=losses,
        level2_equal_within_groups=True, level2_groups_differ=True,
        level1_all_equal=True)
    return state, losses


# ---------------------------------------------------------------------------
# phase 1: full-width training through the launcher
# ---------------------------------------------------------------------------
def phase_train(clog):
    from repro.configs import get_config
    from repro.launch import train
    dev = jax.devices()[0]
    t0 = time.time()
    hist = train.main(train_argv(8))
    losses = [r["loss"] for r in hist]
    check(len(losses) == 8 and all(math.isfinite(v) for v in losses),
          f"train.main losses {losses}")
    log(phase="train", arch=ARCH, reduced=False, steps=8, losses=losses,
        compile_s=clog.take(), wall_s_bring_up_observation=round(
            time.time() - t0, 1), memory=memory([dev]))
    gc.collect()
    eng, model = make_engine(get_config(ARCH), 8, "sim")
    _, lib_losses = check_sync_invariants(eng, model, 0, "sim_invariants")
    log(phase="sim_invariants", compile_s=clog.take(),
        losses_bitwise_equal_to_train_main=same_losses(
            lib_losses, losses[:len(lib_losses)], "sim_invariants"),
        memory=memory([dev]))


# ---------------------------------------------------------------------------
# phase 2: reduced f32 run on the TPU against the host CPU
# ---------------------------------------------------------------------------
# Tolerances.  Both runs are f32 with every matmul at "highest" precision,
# so they differ only by f32 rounding: summation order and the chip's
# transcendental approximations (exp, log, rsqrt), amplified over 8 SGD
# steps.  The bounds sit well above f32 rounding (~1e-7) and below what a
# bf16-pass matmul moves (estimated at ~1e-3 relative in the losses and
# ~1e-3 absolute in the params).  To test that estimate the TPU run is
# repeated at default matmul precision and its gaps to the host are logged
# beside the bounds; they are recorded, not asserted.
LOSS_RTOL = 2e-5
PARAM_ATOL = 5e-5


def phase_numerics():
    from repro.configs import get_config, reduced
    from repro.data import TokenStream
    cfg = reduced(get_config(ARCH))
    check(cfg.param_dtype == "float32" and cfg.dtype == "float32",
          "reduced config is not f32")

    def run(device, precision="highest"):
        with jax.default_device(device), \
                jax.default_matmul_precision(precision):
            eng, model = make_engine(cfg, 8, "sim")
            state = eng.init(jax.random.PRNGKey(0), model.init)
            stream = TokenStream(seed=0, batch=2, seq_len=32,
                                 vocab=cfg.vocab_size, n_workers=4)
            state, hist = eng.run_rounds(state, stream, 8)
            leaf = jax.tree.leaves(state.params)[0]
            check(leaf.devices() == {device},
                  f"reduced run landed on {leaf.devices()}, not {device}")
            return ([r["ce"] for r in hist],
                    jax.tree.map(np.asarray, jax.device_get(state.params)))

    def gaps(losses, params):
        return (max(abs(a - b) / abs(b) for a, b in zip(losses, cpu_losses)),
                max(float(np.max(np.abs(a - b))) for a, b in zip(
                    jax.tree.leaves(params), jax.tree.leaves(cpu_params))))

    tpu_losses, tpu_params = run(jax.devices()[0])
    cpu_losses, cpu_params = run(jax.devices("cpu")[0])
    loss_rel, param_abs = gaps(tpu_losses, tpu_params)
    dflt_loss_rel, dflt_param_abs = gaps(*run(jax.devices()[0], "default"))
    log(phase="numerics_vs_host", config=cfg.name, tpu_losses=tpu_losses,
        cpu_losses=cpu_losses, max_loss_rel_diff=loss_rel,
        max_param_abs_diff=param_abs, loss_rtol=LOSS_RTOL,
        param_atol=PARAM_ATOL, default_precision_tpu={
            "max_loss_rel_diff": dflt_loss_rel,
            "max_param_abs_diff": dflt_param_abs})
    check(loss_rel <= LOSS_RTOL, f"losses differ by {loss_rel} (relative)")
    check(param_abs <= PARAM_ATOL, f"params differ by {param_abs} (absolute)")


# ---------------------------------------------------------------------------
# phase 3: codec kernels compiled at the real bucket width
# ---------------------------------------------------------------------------
# sign scales are f32 means of 1024 |x| summed in another order than the
# oracle's; the worst-case relative rounding of such a sum is 1024 * 2^-24.
SIGN_SCALE_RTOL = 1024 * 2.0 ** -24
# top-k: colliding indices are summed in another order on each backend.
TOPK_ATOL = 1e-5


def phase_kernels(clog):
    from repro.comms.reduce import topk_decode_reduce
    from repro.kernels import comms as K
    from repro.kernels import ref
    L, iblk, sblk = L_BUCKET, 256, 1024
    cpu = jax.devices("cpu")[0]

    @functools.partial(jax.jit, static_argnums=1)
    def bucket(key, rows):
        x = jax.random.normal(key, (rows, L), jnp.bfloat16)
        return x.at[:, :4 * iblk].set(0)  # all-zero blocks: zero scales

    @jax.jit
    def group_scale(x):  # Int8Compressor.reduce's shared group-max scale
        nb = -(-L // iblk)
        amax = jnp.pad(jnp.abs(x), ((0, 0), (0, nb * iblk - L))) \
            .reshape(x.shape[0], nb, iblk).max(-1).astype(jnp.float32)
        return jnp.broadcast_to(amax.max(0, keepdims=True), amax.shape) \
            / 127.0

    @jax.jit
    def int8_mismatches(q, x, scale):
        return jnp.sum(q != ref.int8_scale_quant_ref(x, scale, iblk))

    @jax.jit
    def sign_errors(y, s, x):
        s_ref, rt = ref.sign_ref(x, sblk)
        return (jnp.sum(jnp.signbit(y) != jnp.signbit(rt)),
                jnp.max(jnp.abs(s - s_ref) / jnp.maximum(s_ref, 1e-30)))

    for r in (1, 4):
        x = bucket(jax.random.PRNGKey(r), r)
        scale = group_scale(x)
        t0 = time.time()
        q = jax.block_until_ready(K.int8_scale_quantize(
            x, scale, block=iblk, interpret=False))
        t_q = time.time() - t0
        bad = sum(int(int8_mismatches(q[i:i + 1], x[i:i + 1],
                                      scale[i:i + 1])) for i in range(r))
        check(bad == 0, f"int8_scale_quantize rows={r}: {bad} mismatches")
        del q, scale

        t0 = time.time()
        bits, s = jax.block_until_ready(K.sign_pack(x, block=sblk,
                                                    interpret=False))
        t_s = time.time() - t0
        sign_bad, scale_rel = 0, 0.0
        for i in range(r):
            y = K.sign_unpack(bits[i:i + 1], s[i:i + 1], size=L, block=sblk,
                              interpret=False)
            nbad, rel = sign_errors(y, s[i:i + 1], x[i:i + 1])
            sign_bad, scale_rel = sign_bad + int(nbad), max(scale_rel,
                                                            float(rel))
            del y
        check(sign_bad == 0, f"sign_pack rows={r}: {sign_bad} sign errors")
        check(scale_rel <= SIGN_SCALE_RTOL,
              f"sign_pack rows={r}: scale rel diff {scale_rel}")
        del bits, s, x
        gc.collect()

        k = int(round(L / 16))
        kv, ki = jax.random.split(jax.random.PRNGKey(100 + r))
        vals = jax.random.normal(kv, (r, k), jnp.float32)
        idx = jax.random.randint(ki, (r, k), 0, L, jnp.int32)
        t0 = time.time()
        acc = jax.block_until_ready(topk_decode_reduce(vals, idx, size=L))
        t_t = time.time() - t0
        with jax.default_device(cpu):
            want = jax.jit(ref.topk_reduce_ref, static_argnums=2)(
                jax.device_put(vals, cpu), jax.device_put(idx, cpu), L)
        topk_err = float(np.max(np.abs(np.asarray(acc) - np.asarray(want))))
        check(topk_err <= TOPK_ATOL,
              f"topk decode-reduce rows={r}: max abs diff {topk_err}")
        del vals, idx, acc, want
        gc.collect()
        log(phase="kernels", rows=r, L=L, int8_scale_quantize_mismatches=bad,
            sign_pack_sign_errors=sign_bad, sign_scale_max_rel_diff=scale_rel,
            topk_max_abs_diff=topk_err,
            first_call_s_bring_up_observation={
                "int8_scale_quantize": round(t_q, 2),
                "sign_pack": round(t_s, 2), "topk": round(t_t, 2)},
            compile_s=clog.take(0.5), memory=memory(jax.devices()[:1]))


# ---------------------------------------------------------------------------
# four chips: the mesh executor at full width
# ---------------------------------------------------------------------------
# production mesh syncs reduce in another f32 order than the sim reduce
# that exact=True replays, so a group mean can round to the neighbouring
# bf16 value; the bound is a few bf16 ulps per element (2^-8 relative), and
# the losses may move by far less than that.
MESH_LOSS_RTOL = 1e-3
MESH_PARAM_ULPS = 4


def same_losses(a, b, tag: str) -> bool:
    """The library path must reproduce the launcher's losses; returns
    whether they are bitwise equal (they should be: same programs)."""
    rel = max(abs(x - y) / abs(y) for x, y in zip(a, b))
    check(len(a) == len(b) and rel <= 1e-6,
          f"{tag}: library losses {a} != train.main losses {b}")
    return a == b


def check_spread(state, devices, tag, out):
    """Every worker-axis leaf is split over all devices, one worker each,
    and no device holds much more than another; records bytes in use."""
    jax.block_until_ready(state)  # init's staging copies on device 0 freed
    gc.collect()
    for leaf in jax.tree.leaves(state.params):
        shards = leaf.addressable_shards
        check(leaf.sharding.device_set == set(devices)
              and len({s.device for s in shards}) == len(devices)
              and all(s.data.shape[0] == 1 for s in shards),
              f"{tag}: a params leaf is not one worker per chip")
    used = [d.memory_stats()["bytes_in_use"] for d in devices]
    check(max(used) <= 2 * min(used),
          f"{tag}: bytes in use are lopsided across chips: {used}")
    out.extend(used)


@jax.jit
def _params_close(a, b):
    same, close = [], []
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x32, y32 = x.astype(jnp.float32), y.astype(jnp.float32)
        mag = jnp.maximum(jnp.abs(x32), jnp.abs(y32))
        ulp = jnp.exp2(jnp.floor(jnp.log2(jnp.maximum(mag, 1e-30))) - 7)
        same.append(jnp.array_equal(x, y))
        close.append(jnp.all(jnp.abs(x32 - y32) <= MESH_PARAM_ULPS * ulp))
    return jnp.all(jnp.stack(same)), jnp.all(jnp.stack(close))


def params_close(a, b):
    """(bitwise equal, within MESH_PARAM_ULPS bf16 ulps everywhere)."""
    same, close = _params_close(a, b)
    return bool(same), bool(close)


def phase_mesh(clog):
    from repro.configs import get_config
    from repro.core import MeshExecutor
    from repro.launch import train
    devices = jax.devices()[:4]
    steps = HIER["G"]
    for comms in (None, "int8"):
        tag = f"mesh_{comms or 'off'}"
        extra = ["--backend", "mesh"] + (["--comms", comms] if comms else [])
        hist = train.main(train_argv(steps, *extra))
        main_losses = [r["loss"] for r in hist]
        check(all(math.isfinite(v) for v in main_losses),
              f"{tag}: train.main losses {main_losses}")
        log(phase=tag, entry="train.main", losses=main_losses,
            compile_s=clog.take(), memory=memory(devices))
        gc.collect()

        cfg = get_config(ARCH)
        eng, model = make_engine(cfg, steps, "mesh", comms)
        used = []
        prod, prod_losses = check_sync_invariants(
            eng, model, 0, f"{tag}_production",
            on_init=lambda st: check_spread(st, devices, tag, used))
        lib_bitwise = same_losses(prod_losses, main_losses, tag)
        prod_params = prod.params
        del prod
        gc.collect()
        eng_x, model_x = make_engine(cfg, steps, MeshExecutor(exact=True),
                                     comms)
        exact, exact_losses = check_sync_invariants(eng_x, model_x, 0,
                                                    f"{tag}_exact")
        loss_rel = max(abs(a - b) / abs(b)
                       for a, b in zip(prod_losses, exact_losses))
        bitwise, close = params_close(prod_params, exact.params)
        log(phase=tag, library_losses_bitwise_equal_to_train_main=lib_bitwise,
            production_vs_exact={
                "max_loss_rel_diff": loss_rel, "params_bitwise": bitwise,
                f"params_within_{MESH_PARAM_ULPS}_bf16_ulps": close},
            bytes_in_use_after_init=used, compile_s=clog.take(),
            memory=memory(devices))
        check(loss_rel <= MESH_LOSS_RTOL,
              f"{tag}: production vs exact losses differ by {loss_rel}")
        check(close, f"{tag}: production vs exact params beyond "
                     f"{MESH_PARAM_ULPS} bf16 ulps")
        del prod_params, exact
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip mesh phase")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        raise SmokeFailure(f"no repro package at {SRC}; run this script "
                           "from a checkout of the repository")
    sys.path.insert(0, str(SRC))
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SmokeFailure(
            f"no TPU found: jax.devices()[0].platform is "
            f"{devices[0].platform!r}; this script runs only on the chip")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs {args.chips} TPU devices, found "
          f"{len(devices)}")
    from repro.launch.cache import enable_compile_cache
    dev = devices[0]
    log(device_kind=dev.device_kind, device_count=len(devices),
        compile_cache=enable_compile_cache())
    clog = CompileLog()
    if args.chips == 4:
        phase_mesh(clog)
    else:
        phase_train(clog)
        gc.collect()
        phase_numerics()
        gc.collect()
        phase_kernels(clog)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
