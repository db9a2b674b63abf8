"""A configuration brings its model family by name
(``bench/models/<model>.py``): the dense family reads as the benchmark read
before families were split out (shapes, weights and the reference pinned
at values taken from the earlier code), a configuration without a family
is refused, and a second family is new files only."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchtest_support as sup
from bench import generator, harness, program, reference, weights

BENCH = sup.REPO / "bench"
DENSE = harness.load_model(BENCH, {"model": "dense"})
KEY = 12345

SHAPES = {
    "deepseek-7b-1L": {
        "embed": (8192, 4096), "head": (4096, 8192), "final_ln": (4096,),
        "layers": {
            "ln1": (1, 4096), "ln2": (1, 4096),
            "attn": {"wq": (1, 4096, 32, 128), "wk": (1, 4096, 32, 128),
                     "wv": (1, 4096, 32, 128), "wo": (1, 32, 128, 4096)},
            "mlp": {"w_gate": (1, 4096, 11008), "w_up": (1, 4096, 11008),
                    "w_down": (1, 11008, 4096)}}},
    "h2o-danube-1.8b-2L": {
        "embed": (32000, 2560), "head": (2560, 32000), "final_ln": (2560,),
        "layers": {
            "ln1": (2, 2560), "ln2": (2, 2560),
            "attn": {"wq": (2, 2560, 32, 80), "wk": (2, 2560, 8, 80),
                     "wv": (2, 2560, 8, 80), "wo": (2, 32, 80, 2560)},
            "mlp": {"w_gate": (2, 2560, 6912), "w_up": (2, 2560, 6912),
                    "w_down": (2, 6912, 2560)}}},
}
# The tiny configuration at jax.random.PRNGKey(KEY), leaves in tree order:
# embed, final_ln, head, layers/attn/{wk, wo, wq, wv}, layers/{ln1, ln2},
# layers/mlp/{w_down, w_gate, w_up}. Float64 norms of the float32 weights.
WEIGHT_NORMS = [5.155080032486798, 11.313708498984761, 19.893615815528534,
                14.107681176693697, 14.051785344466213, 14.087920256644873,
                14.074241132641456, 16.0, 16.0, 14.100805366611718,
                20.042180778329335, 19.923098671382157]
# The reference's loss and gradient leaf norms on the first batch of the
# Markov feed from seed 2**33 + 7 (4 x 16 tokens, fan-out 8).
LOSS = 6.634549617767334
GRAD_NORMS = [9.484922310984869, 0.13308079431979175, 1.4417200651129232,
              1.0143726370738302, 2.2714175149735283, 1.0008892280701382,
              2.2732537066156553, 0.212718643912618, 0.142719947620661,
              1.8003921047173, 1.3447380985251138, 1.2841103625407078]


def norms(tree) -> list:
    return [float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))
            for x in jax.tree.leaves(tree)]


def tiny_tokens():
    feed = generator.markov_batches(2 ** 33 + 7, sup.TINY["vocab_size"], 4,
                                    16, 8)
    return jnp.asarray(next(feed))


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_committed_configs_name_the_dense_family(name):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    model = harness.load_model(BENCH, cfg)
    assert pathlib.Path(model.__file__) == BENCH / "models" / "dense.py"
    assert model.shapes(cfg) == SHAPES[name]


def test_weights_are_as_pinned():
    params = weights.make(DENSE, sup.TINY, jax.random.PRNGKey(KEY))
    assert norms(params) == WEIGHT_NORMS


def test_reference_loss_and_gradient_are_as_pinned():
    params = weights.make(DENSE, sup.TINY, jax.random.PRNGKey(KEY))
    loss, grad = reference.value_and_grad(DENSE, params, tiny_tokens(),
                                          sup.TINY)
    assert float(loss) == pytest.approx(LOSS, rel=1e-6)
    assert norms(grad) == pytest.approx(GRAD_NORMS, rel=1e-6)


def test_a_configuration_without_a_family_is_refused(tmp_path):
    root = sup.tiny_checkout(tmp_path)
    cfg = {k: v for k, v in sup.TINY.items() if k != "model"}
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match="names no model family"):
        harness.load_cell("tiny-train", root)


# A second family: the dense module with one more leaf, ``proj`` [d, d],
# which multiplies the final norm's output before the head, and a width
# of its own, ``proj_size``, checked against the program's model width.
PROJ = '''

_dense_shapes, _dense_logits, _dense_check = shapes, logits, check
_dense_matmul_params = matmul_params


def shapes(cfg):
    return dict(_dense_shapes(cfg), proj=(cfg["proj_size"],
                                          cfg["hidden_size"]))


def check(cfg, c):
    _dense_check(cfg, c)
    if cfg["proj_size"] != c.d_model:
        raise ValueError(f"proj_size {cfg['proj_size']} != {c.d_model}")


def logits(params, tokens, cfg, quant=None):
    head = mm("de,ev->dv", params["proj"], params["head"], quant)
    return _dense_logits(dict(params, head=head), tokens, cfg, quant)


def matmul_params(cfg):
    return _dense_matmul_params(cfg) + cfg["proj_size"] * cfg["hidden_size"]
'''


def add_cell(root: pathlib.Path, model: str, model_text: str,
             extra: dict) -> str:
    """Add, as new files and new entries only, a configuration of the family
    ``model`` and a train cell of it; returns the cell's name."""
    b = root / "bench"
    (b / "models" / f"{model}.py").write_text(model_text)
    cfg = dict(sup.TINY, name=f"tiny-{model}", model=model, **extra)
    (b / "configs" / f"tiny-{model}.json").write_text(json.dumps(cfg))
    cell = f"tiny-{model}-train"
    (b / "traffic" / f"{cell}.json").write_text(json.dumps(sup.TRAIN))
    (b / "limits" / f"{cell}.json").write_text(
        json.dumps(sup.LIMITS["tiny-train"]))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": cfg["name"], "source": "test",
                            "file": f"bench/configs/tiny-{model}.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": cell, "config": cfg["name"],
                              "traffic": cell, "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return cell


def test_a_second_family_is_new_files_only(tmp_path):
    root = sup.tiny_checkout(tmp_path)
    dense_text = (root / "bench" / "models" / "dense.py").read_text()
    name = add_cell(root, "dense_proj", dense_text + PROJ, {"proj_size": 128})
    cell = harness.load_cell(name, root)
    cfg, model = cell.config, cell.model
    assert pathlib.Path(model.__file__) == (
        root / "bench" / "models" / "dense_proj.py")

    # the weights' layout is the module's
    params = weights.make(model, cfg, jax.random.PRNGKey(KEY))
    assert params["proj"].shape == (128, 128)
    assert 0 < float(jnp.std(params["proj"])) < 1.0 / 128 ** 0.5

    # the reference's forward is the module's: with proj the identity it
    # is the dense forward, and with the random proj it is not
    tokens = tiny_tokens()
    base = {k: v for k, v in params.items() if k != "proj"}
    dense_loss = float(reference.loss(DENSE, base, tokens, cfg))
    eye = dict(params, proj=jnp.eye(128, dtype=jnp.float32))
    assert float(reference.loss(model, eye, tokens, cfg)) == (
        pytest.approx(dense_loss, rel=1e-6))
    loss, grad = reference.value_and_grad(model, params, tokens, cfg)
    assert float(loss) != pytest.approx(dense_loss, rel=1e-3)
    assert float(jnp.linalg.norm(grad["proj"])) > 0

    # the operation count is the module's, and mfu.train reads it
    extra = 6 * 128 * 128 * 4 * 16
    assert model.train_step_flops(cfg, 4, 16) == (
        DENSE.train_step_flops(cfg, 4, 16) + extra)
    out = harness.Outcome(end_to_end={}, checks=[], attempted=1, failed=0,
                          memory_peak_bytes=0,
                          counters={"steps": 1, "window_s": 0.02,
                                    "batch": 4, "seq": 16})
    run = harness.Run(cell, out, sup.CPU_PEAKS, 1)
    mfu = harness.load_reader(cell, "mfu.train").read(run)
    assert mfu == pytest.approx(100.0 * model.train_step_flops(cfg, 4, 16)
                                / 0.02 / sup.CPU_PEAKS["bf16_flops_per_s"])

    # the width check is the module's: a width dense does not have is held
    # against the program, and so is every dense width
    program.model_api(model, cfg)
    for wrong in ({"proj_size": 64}, {"hidden_size": 96}):
        with pytest.raises(ValueError):
            program.model_api(model, dict(cfg, **wrong))


def test_a_copied_family_checks_as_dense(tmp_path):
    root = sup.tiny_checkout(tmp_path)
    dense_text = (root / "bench" / "models" / "dense.py").read_text()
    name = add_cell(root, "dense_copy", dense_text, {})
    assert harness.load_cell(name, root).model.__file__.endswith(
        "dense_copy.py")
    copy = sup.run(root, name, seconds=0.5)
    dense = sup.run(root, "tiny-train", seconds=0.5)
    assert copy["correct"] is True
    assert copy["checks"] == dense["checks"]
