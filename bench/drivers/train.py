"""Training driver: the program's stale-psum engine under the Trainer, as
``launch/train.py`` runs it, on the traffic file's batches.

Set-up builds one engine and one state from the seed, drives them through
the first ``check_steps`` steps (these compile, and are read for the
correctness check), and hands the same engine and state to the window. The
window runs ``Trainer.run`` until ``--seconds`` have passed, with the host
syncing only where ``launch/train.py`` does (a log row every ``log_every``
steps). ``train_tokens_per_s`` is every worker's tokens in the steps the
window completed, over the window's wall time, device work included.

Correctness, once the window has closed and the program's state is freed:
the plain float32 reference (``bench/reference.py`` with the family's
forward, ``bench/models/<model>.py``) follows the same first
steps from the same seed (enough of them that the ring's slots are written
twice and read back), and these numbers are held to their limits:

* ``loss_gap``: the largest relative gap of a step's mean loss, where the
  cell's limits file gives it a limit;
* ``grad_gap``: the first delivered gradient, as Adam's first moment holds
  it after one step (m / (1 - b1)), by the worst leaf's norm;
* ``change_gap``: the parameters' change over the first steps, by the worst
  leaf's norm, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's;
* ``grad_diff``: the first delivered gradient against the reference's, by
  the worst leaf's norm of their difference. The norm gaps above cannot
  tell bfloat16 from float8 arithmetic (a norm moves only by the square of
  a random per-element error); the difference can (``PERF.md``).

``loss_gap`` has a limit only where a planted fault reads far enough above
sound runs for one to separate them; elsewhere it is reported beside the
others (``PERF.md``).
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import generator, harness, program, reference, weights
from repro.engine import Hook



def build(cell, devices):
    """(engine, api) of the program for this cell."""
    import jax.numpy as jnp
    from repro.configs.base import InputShape
    from repro.launch import mesh as meshlib
    from repro.launch.train import build_train_engine
    cfg, tr = cell.config, cell.traffic
    arch, api = program.model_api(cell.model, cfg)
    mesh = meshlib.parse_host_mesh(tr["mesh"])
    if mesh.devices.size != len(devices):
        raise ValueError(f"mesh {tr['mesh']} spans {mesh.devices.size} "
                         f"devices; the cell has {len(devices)}")
    shape = InputShape(f"bench_{cell.name}", tr["seq"], tr["batch"], "train")
    engine = build_train_engine(
        api, arch, mesh, shape, lr=cfg["optimizer"]["lr"],
        mode=tr["mode"], num_workers=tr["workers"], s=tr["staleness"],
        buffer_dtype=jnp.dtype(tr["ring_dtype"]))
    return engine, api


class FirstSteps(Hook):
    """Hook that reads the program's state during the first steps: each
    step's loss, the first delivered gradient from Adam's first moment
    after step 1, and the parameters' change after the last checked step
    (against the seed's weights made again)."""

    def __init__(self, cell, key_seed: int, steps: int):
        self.cell, self.key_seed, self.steps = cell, key_seed, steps
        self.losses, self.first_grad, self.change = [], None, None

    def on_step(self, ctx):
        import jax
        if ctx.step >= self.steps:
            return
        self.losses.append(ctx.metrics["loss"])
        inner = ctx.state.inner
        if ctx.step == 0:
            b1 = self.cell.config["optimizer"]["b1"]
            m = inner.opt_state["m"]
            self.first_grad = reference.leaf_norms(m) / (1.0 - b1)
            self.first_tree = jax.tree.map(
                lambda x: np.asarray(x, np.float32) / (1.0 - b1),
                jax.device_get(m))
        if ctx.step == self.steps - 1:
            params0 = weights.make(self.cell.model, self.cell.config,
                                   jax.random.PRNGKey(self.key_seed))
            self.change = reference.leaf_change_norms(inner.params, params0)
            del params0
            jax.block_until_ready(self.change)

    def readings(self) -> dict:
        return {"losses": [float(x) for x in self.losses],
                "first_grad": np.asarray(self.first_grad),
                "change": np.asarray(self.change),
                "first_tree": self.first_tree}


class LogClock(Hook):
    """Host clock at each log row, where the Trainer syncs: how steady the
    window ran, for the log."""

    def on_start(self, ctx):
        self.ticks = [time.monotonic()]

    def on_log(self, ctx):
        self.ticks.append(time.monotonic())

    def summary(self) -> str:
        d = np.diff(self.ticks) * 1e3
        if not len(d):
            return "no log rows"
        return (f"ms between log rows: median {np.median(d):.2f}, "
                f"max {d.max():.2f}, over 1.5x median "
                f"{int((d > 1.5 * np.median(d)).sum())} of {len(d)}")


def first_steps(engine, cell, seed: int):
    """Build the state from ``seed`` and drive it through the first steps.
    Returns (state, readings hook, batch feed)."""
    import jax
    from repro.engine import Trainer
    cfg, tr = cell.config, cell.traffic
    ks = program.key_seed(seed)
    # The state takes (and the step donates) its own key arrays.
    state = engine.init(jax.random.PRNGKey(ks),
                        params=weights.make(cell.model, cfg,
                                            jax.random.PRNGKey(ks)))
    feed = generator.markov_batches(seed, cfg["vocab_size"], tr["batch"],
                                    tr["seq"], tr["data"]["fan_out"])
    rec = FirstSteps(cell, ks, tr["check_steps"])
    result = Trainer(engine, hooks=[rec]).run(
        lambda: {"tokens": next(feed)}, tr["check_steps"], state=state,
        log_every=tr["log_every"])
    jax.block_until_ready(result.state)
    return result.state, rec, feed


def reference_readings(cell, seed: int, *, quant=None, fault: str = "none"):
    """The reference's readings of the first steps from ``seed``; with
    ``quant`` or a ``fault`` the control or a planted fault."""
    import jax
    cfg, tr = cell.config, cell.traffic
    ks = program.key_seed(seed)
    feed = generator.markov_batches(seed, cfg["vocab_size"], tr["batch"],
                                    tr["seq"], tr["data"]["fan_out"])
    batches = [next(feed) for _ in range(tr["check_steps"])]
    delays = reference.uniform_delays(ks, tr["check_steps"], tr["workers"],
                                      tr["staleness"])
    params0 = weights.make(cell.model, cfg, jax.random.PRNGKey(ks))
    per = tr["batch"] // tr["workers"]
    out = reference.stale_psum(
        cell.model, params0, batches, delays, cfg, cfg["optimizer"],
        quant=quant,
        rows=slice(0, per // 2) if fault == "half_batch" else None,
        own_worker=0 if fault == "own_worker" else None)
    out["delays"] = delays.tolist()
    return out


CHECKED = ("grad_gap", "change_gap", "grad_diff")


def compare(prog: dict, ref: dict) -> dict:
    """The numbers read against the reference (``CHECKED``, and
    ``loss_gap`` where it has a limit, are held to limits)."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    keep = reference.moving_leaves(ref["first_grad"])
    diff = reference.leaf_diff_norms(prog["first_tree"], ref["first_tree"])
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_gap": reference.worst_leaf_gap(prog["first_grad"],
                                             ref["first_grad"]),
        "change_gap": reference.worst_leaf_gap(prog["change"], ref["change"],
                                               keep),
        "grad_diff": reference.worst_leaf_share(diff, ref["first_grad"]),
    }


def run(ctx: harness.Context) -> harness.Outcome:
    import jax
    from repro.engine import Trainer
    cell, tr = ctx.cell, ctx.cell.traffic
    engine, _ = build(cell, ctx.devices)
    ctx.log(f"engine: {engine.dispatch_report()}")
    state, rec, feed = first_steps(engine, cell, ctx.seed)
    setup_s = time.monotonic() - ctx.started
    compiles = program.CompileCounter()

    deadline = [None]
    steps = [0]

    def window_batch():
        if time.monotonic() >= deadline[0]:
            raise StopIteration
        steps[0] += 1
        return {"tokens": next(feed)}

    box, blocks = {}, LogClock()
    with compiles, program.profiled(ctx.trace, len(ctx.devices), box):
        deadline[0] = time.monotonic() + ctx.seconds
        result = Trainer(engine, hooks=[blocks]).run(
            window_batch, 1 << 40, state=state, log_every=tr["log_every"])
        jax.block_until_ready(result.state)
    window_s = box["window_s"]
    tokens = steps[0] * tr["batch"] * tr["seq"]
    ctx.log(f"window: {steps[0]} steps in {window_s!r} s; "
            f"compiles in window: {compiles.count} {compiles.names}; "
            f"{blocks.summary()}")
    peak = program.memory_peak_bytes(ctx.devices)
    prog = rec.readings()
    del state, result, rec
    gc.collect()

    ref = reference_readings(cell, ctx.seed)
    gaps = compare(prog, ref)
    limits = ctx.cell.limits
    held = ("loss_gap",) * ("loss_gap" in limits) + CHECKED
    ctx.log(f"losses program {prog['losses']} reference {ref['losses']}; "
            f"delays {ref['delays']}; loss_gap {gaps['loss_gap']!r}"
            + ("" if "loss_gap" in limits else " (not held to a limit)"))
    checks = [(name, gaps[name], limits[name]) for name in held]
    return harness.Outcome(
        end_to_end={"train_tokens_per_s": tokens / window_s,
                    "setup_s": setup_s},
        checks=checks, attempted=steps[0], failed=0,
        memory_peak_bytes=peak,
        counters={"steps": steps[0], "window_s": window_s,
                  "batch": tr["batch"], "seq": tr["seq"],
                  "compiles_in_window": compiles.count},
        trace=box.get("summary"))
