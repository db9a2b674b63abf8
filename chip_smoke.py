"""Bring-up check: the staleness engine and the serving plane on one TPU.

    python chip_smoke.py              # one chip: a train phase, then a serve phase
    python chip_smoke.py --chips 4    # four chips: stale-psum over a data=4 mesh

Both phases run deepseek-7b at its published widths (d_model 4096, 32 heads x
head_dim 128, kv 32, d_ff 11008) through the entry points a user calls; only
depth and vocabulary are cut, to what one v5e's 16 GB holds (``CUT``).
Weights are random, made from a seed.

* train: ``launch/train.py``'s engine (``build_train_engine``) and the
  ``Trainer`` run ``stale-psum`` with P=2 workers, s=1, kernels on and the
  one-pass megakernel, on ``token_lm_stream`` data. The first steps' losses
  must match a kernels-off run of the same config.
* serve: a ``Server`` with the paged decode route answers 8 requests
  greedily; its tokens must equal the gather route's (``paged="off"``) and
  the page-table attention kernel must run compiled.
* ``--chips 4``: only stale-psum with P=4, one worker per chip on a ``4x1``
  mesh, compared with the same steps on a ``1x1`` mesh; every device must
  hold a shard of the per-worker state. Kernels are off there: XLA cannot
  partition a compiled Mosaic kernel over several chips.

Every kernel a phase uses must run compiled (``pallas``): a ``ref`` or
``pallas-interpret`` dispatch fails the check. The script exits nonzero and
prints no result when JAX finds no TPU. Its last stdout line is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro import configs as cfglib  # noqa: E402
from repro import treemath as tm  # noqa: E402
from repro.configs.base import InputShape  # noqa: E402
from repro.engine import Hook, Trainer  # noqa: E402
from repro.kernels import dispatch  # noqa: E402
from repro.launch import mesh as meshlib  # noqa: E402
from repro.launch.train import build_train_engine, make_batch_fn  # noqa: E402
from repro.serving import Server, ServingConfig, synthetic_requests  # noqa: E402

ARCH = "deepseek-7b"
# Published widths, depth and vocabulary cut. Sized with
# compiled.memory_analysis() of the planned kernels-on step for a described
# v5e: 1 layer at vocab 8192 needs 11.05 GB of the 15.75 GB; 1 layer at
# vocab 32768 needs 17.54 GB and is refused.
CUT = {"num_layers": 1, "vocab": 8192, "vocab_real": 8192}
SEED = 0
TRAIN = dict(batch=4, seq=128, steps=5, check_steps=3)
# The tolerance of the CPU kernels-on/off matrix (tests/test_engine_matrix).
LOSS_RTOL = 1e-4
# 4x1 against 1x1: the same math with the worker mean reduced across chips
# and bf16 activations tiled differently. Adam moves each coordinate by
# about lr whatever its gradient, so a sign that flips on rounding moves a
# parameter by up to 2 lr; those stay rare (1.7e-3 relative L2 after 3
# steps on a v5e). The first step's ring holds raw bf16 gradients at
# identical parameters; a worker's row misrouted or lost there reads 0.5
# or more.
LOSS_RTOL_4 = 1e-3
STATE_REL_L2_4 = 1e-2
SERVE = dict(slots=4, prompt_len=128, max_seq=160, prefill_batch=4)
REQUESTS, GEN = 8, 32


def log(msg: str) -> None:
    print(msg, flush=True)


def kernel_faults(decisions: dict) -> list:
    """Dispatch entries that did not run a compiled kernel."""
    return [f"{op} -> {backend}" for op, backend in decisions.items()
            if backend.split()[0] in ("ref", "pallas-interpret")]


def _peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


class StepClock(Hook):
    """Host clock at the end of every logged step. The Trainer turns each
    logged loss into a float before ``on_log``, so the step has finished on
    the device when the clock is read."""

    def on_start(self, ctx) -> None:
        self.ticks = [time.perf_counter()]

    def on_log(self, ctx) -> None:
        self.ticks.append(time.perf_counter())


def run_train(*, reduced: bool, cut, workers: int, mesh_spec: str = "1x1",
              kernels: str = "on", steps: int = TRAIN["steps"],
              batch: int = TRAIN["batch"], seq: int = TRAIN["seq"],
              keep_state: bool = False, hooks=()) -> dict:
    """stale-psum (s=1) through ``launch/train.py``'s engine and ``Trainer``.
    Returns losses, per-step wall times, the engine's kernel report and,
    with ``keep_state``, the final engine state."""
    arch = cfglib.get(ARCH)
    api = arch.api(reduced=reduced, overrides=cut)
    mesh = meshlib.parse_host_mesh(mesh_spec)
    shape = InputShape(f"smoke_train_{seq}", seq, batch, "train")
    dispatch.reset_report()
    engine = build_train_engine(
        api, arch, mesh, shape, mode="stale-psum", num_workers=workers, s=1,
        kernels=kernels, buffer_dtype=jnp.bfloat16)
    state = engine.init(jax.random.PRNGKey(SEED))
    n_params = tm.tree_size(engine.params(state))
    clock = StepClock()
    result = Trainer(engine, hooks=[clock, *hooks]).run(
        make_batch_fn(api, batch, seq, SEED), steps, state=state, log_every=1)
    del state
    walls = np.diff(clock.ticks)
    out = {"losses": [row["loss"] for row in result.history],
           "step_s": walls.tolist(), "first_step_s": float(walls[0]),
           "steady_step_s": (float(np.mean(walls[1:]))
                             if len(walls) > 1 else None),
           "n_params": n_params, "report": engine.dispatch_report(),
           "mesh": mesh}
    if keep_state:
        out["state"] = result.state
    return out


def train_phase(*, reduced: bool = False, cut=CUT, workers: int = 2) -> dict:
    """The kernel path trains, and its first steps match kernels off."""
    on = run_train(reduced=reduced, cut=cut, workers=workers, kernels="on")
    off = run_train(reduced=reduced, cut=cut, workers=workers, kernels="off",
                    steps=TRAIN["check_steps"])
    k = TRAIN["check_steps"]
    problems = []
    if not all(np.isfinite(on["losses"])):
        problems.append(f"non-finite losses {on['losses']}")
    if not np.allclose(on["losses"][:k], off["losses"], rtol=LOSS_RTOL,
                       atol=0.0):
        problems.append(f"kernels on {on['losses'][:k]} != off "
                        f"{off['losses']} (rtol {LOSS_RTOL})")
    rep = on["report"]
    if rep.get("delivery") != "packed" or rep.get("megakernel") != "fused":
        problems.append(f"kernel path not engaged: {rep}")
    return {"on": on, "off": off, "problems": problems}


def serve_phase(*, reduced: bool = False, cut=CUT) -> dict:
    """The paged route answers the requests at the configured precision,
    and at float32 with full-precision matmuls its greedy tokens equal the
    gather route's. At bfloat16 the routes round at different points (the
    kernel keeps its softmax weights in float32, the gather route casts
    them to bfloat16), so a near-tie in the top logit of random weights can
    flip a token: agreement there is reported, not required."""
    def serve(paged: str, overrides, params):
        cfg = ServingConfig(reduced=reduced, overrides=overrides, paged=paged,
                            temperature=0.0, seed=SEED, **SERVE)
        dispatch.reset_report()
        server = Server(cfg, params=params)
        reqs = synthetic_requests(REQUESTS, cfg.prompt_len, GEN,
                                  server.api.vocab_real, seed=SEED + 1)
        t0 = time.monotonic()
        server.run(reqs)                  # compiles prefill + decode
        warm_s = time.monotonic() - t0
        report = server.run(reqs)
        tokens = {r.rid: r.tokens for r in report.completed}
        return server.params, {
            "tokens": tokens, "summary": report.summary(), "warm_s": warm_s,
            "report": server.dispatch_report()}

    exact = dict(cut or {}, dtype=jnp.float32)
    runs, params = {}, None
    for name, paged, overrides in (("paged", "on", cut),
                                   ("gather", "off", cut),
                                   ("paged_f32", "on", exact),
                                   ("gather_f32", "off", exact)):
        precision = "highest" if overrides is exact else None
        with jax.default_matmul_precision(precision):
            params, runs[name] = serve(paged, overrides, params)
        gc.collect()

    problems = []
    for name in ("paged", "paged_f32"):
        run = runs[name]
        if len(run["tokens"]) != REQUESTS or any(
                len(t) != GEN for t in run["tokens"].values()):
            problems.append(f"{name} route served {run['summary']}")
        if run["report"]["paged"] != "paged":
            problems.append(f"{name} serve route {run['report']}")
    differences = {
        "bf16": _token_differences(runs["paged"], runs["gather"]),
        "f32": _token_differences(runs["paged_f32"], runs["gather_f32"])}
    if differences["f32"]:
        problems.append(f"float32 paged != gather tokens; request -> first "
                        f"differing position: {differences['f32']}")
    return {"runs": runs, "differences": differences, "problems": problems}


def _token_differences(a: dict, b: dict) -> dict:
    """request id -> first position where two runs' tokens differ."""
    out = {}
    for rid, want in sorted(b["tokens"].items()):
        got = a["tokens"].get(rid) or []
        if got != want:
            out[rid] = next((i for i, (x, y) in enumerate(zip(got, want))
                             if x != y), min(len(got), len(want)))
    return out


def shard_devices(state) -> dict:
    """device id -> bytes of per-worker state (gradient ring) it holds."""
    held: dict = {}
    for leaf in jax.tree.leaves(state.inner.gbuf):
        for shard in leaf.addressable_shards:
            held[shard.device.id] = (held.get(shard.device.id, 0)
                                     + shard.data.nbytes)
    return held


def four_chip_phase(*, reduced: bool = False, cut=CUT) -> dict:
    """stale-psum, one worker per chip on data=4, against the same steps on
    one chip (1x1). Kernels off on both: XLA cannot partition a compiled
    Mosaic kernel, so on a mesh of several chips the engine delivers
    through the tree ring (``kernel_placement_ok``)."""
    kw = dict(reduced=reduced, cut=cut, workers=4, kernels="off",
              steps=TRAIN["check_steps"], keep_state=True)
    first = FirstRing()
    wide = run_train(mesh_spec="4x1", hooks=[first], **kw)
    held = shard_devices(wide["state"])
    per_device = {d.id: int((d.memory_stats() or {}).get("bytes_in_use", -1))
                  for d in jax.devices()}
    wide_state = _compared(wide.pop("state"), first.ring)
    gc.collect()
    one = run_train(mesh_spec="1x1", hooks=[first], **kw)
    one_state = _compared(one.pop("state"), first.ring)
    problems = []
    if not np.allclose(wide["losses"], one["losses"], rtol=LOSS_RTOL_4,
                       atol=0.0):
        problems.append(f"4x1 losses {wide['losses']} != 1x1 "
                        f"{one['losses']} (rtol {LOSS_RTOL_4})")
    rel = {name: _rel_l2(wide_state[name], one_state[name])
           for name in wide_state}
    for name in ("first_ring", "params"):
        if not rel[name] <= STATE_REL_L2_4:
            problems.append(f"4x1 {name} differ from 1x1 by {rel[name]} "
                            f"(relative L2 > {STATE_REL_L2_4})")
    ndev = len(wide["mesh"].devices.flat)
    if len(held) != ndev or len(set(held.values())) != 1:
        problems.append(f"per-worker ring not sharded over {ndev} devices: "
                        f"{held}")
    short = {d: b for d, b in per_device.items() if b < held.get(d, 1)}
    if short:
        problems.append(f"bytes in use below the device's ring shard: "
                        f"{short}")
    return {"wide": wide, "one": one, "held": held, "per_device": per_device,
            "rel_l2": rel, "problems": problems}


class FirstRing(Hook):
    """Host copy of the gradient ring after the first step: every worker's
    raw gradient, taken at the same initial parameters on any mesh."""

    def on_step(self, ctx) -> None:
        if ctx.step == 0:
            self.ring = jax.device_get(ctx.state.inner.gbuf)


def _compared(state, first_ring) -> dict:
    """What the 4-chip check compares, on the host: the first step's ring,
    the final parameters, and the final ring. Only the first two are held
    to ``STATE_REL_L2_4``; the final ring's gradients are taken at
    parameters that have already drifted apart, and it is reported."""
    return {"first_ring": first_ring,
            **jax.device_get({"params": state.inner.params,
                              "last_ring": state.inner.gbuf})}


def _rel_l2(got, want) -> float:
    """||got - want|| / ||want|| over all leaves of two host trees."""
    num = den = 0.0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        num += float(np.sum(np.square(a - b)))
        den += float(np.sum(np.square(b)))
    return (num / den) ** 0.5


def _print_train(tag: str, run: dict) -> None:
    log(f"{tag}: params {run['n_params']} ({run['n_params'] / 1e6:.1f}M)")
    log(f"{tag}: losses {run['losses']}")
    log(f"{tag}: first step (compile + run) {run['first_step_s']} s, "
        f"mean of the rest {run['steady_step_s']} s/step; "
        f"each step {run['step_s']}")
    rep = run["report"]
    log(f"{tag}: delivery={rep.get('delivery')} "
        f"megakernel={rep.get('megakernel')}")
    for op, backend in rep["decisions"].items():
        log(f"  {op:<16} -> {backend}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    if dispatch.CONFIG.interpret:
        log("chip_smoke: REPRO_KERNELS_INTERPRET forces interpret mode")
        return 2
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        log(f"chip_smoke: no TPU (jax.devices()[0].platform={dev.platform})")
        return 2
    if len(jax.devices()) < args.chips:
        log(f"chip_smoke: --chips {args.chips} but jax sees "
            f"{len(jax.devices())} device(s)")
        return 2
    log(f"compile cache: {compile_cache.enable()}")
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")

    log(f"cut: {ARCH} {CUT} (published widths; depth and vocab cut)")
    faults = []
    if args.chips == 4:
        res = four_chip_phase()
        for tag in ("wide", "one"):
            _print_train(f"4chip[{tag}]", res[tag])
            faults += kernel_faults(res[tag]["report"]["decisions"])
        log(f"4chip: ring bytes per device {res['held']}")
        log(f"4chip: bytes_in_use per device {res['per_device']}")
        log(f"4chip: relative L2 4x1 vs 1x1 {res['rel_l2']}")
        faults += res["problems"]
        count = 4
    else:
        tr = train_phase()
        _print_train("train", tr["on"])
        _print_train("train[kernels off]", tr["off"])
        log(f"train: peak_bytes_in_use {_peak_bytes()}")
        faults += kernel_faults(tr["on"]["report"]["decisions"])
        faults += tr["problems"]
        del tr
        gc.collect()

        sv = serve_phase()
        for name, run in sv["runs"].items():
            log(f"serve[{name}]: first pass (compile + run) {run['warm_s']} s")
            log(f"serve[{name}]: {json.dumps(run['summary'])}")
        for dtype, diff in sv["differences"].items():
            log(f"serve: {dtype} paged vs gather: "
                f"{REQUESTS - len(diff)}/{REQUESTS} requests identical; "
                f"request -> first differing position: {diff}")
        paged_ops = sv["runs"]["paged"]["report"]["decisions"]
        for op, backend in paged_ops.items():
            log(f"  {op:<16} -> {backend}")
        log(f"serve: tokens rid 0 {sv['runs']['paged']['tokens'].get(0)}")
        log(f"serve: peak_bytes_in_use {_peak_bytes()}")
        for name in ("paged", "paged_f32"):
            faults += kernel_faults(sv["runs"][name]["report"]["decisions"])
        if paged_ops.get("paged_attention", "").split()[:1] != ["pallas"]:
            faults.append(f"paged_attention -> "
                          f"{paged_ops.get('paged_attention')}")
        faults += sv["problems"]
        count = 1

    if faults:
        for f in faults:
            log(f"chip_smoke: FAIL: {f}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
