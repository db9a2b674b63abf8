"""How the benchmark reaches the system under test: the program's model
built from a configuration file through its family's module, with every
width checked against the file, and the helpers the drivers share (seeds,
memory, the profiler window)."""
from __future__ import annotations

import contextlib
import shutil
import tempfile
import time

import numpy as np


def key_seed(seed: int) -> int:
    """A 31-bit JAX key seed from any whole number (the driver's seeds do
    not fit 32 signed bits)."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0] >> 1)


def model_api(model, cfg: dict):
    """(arch, api) of the program for configuration ``cfg`` of the family
    ``model`` (``bench/models/<model>.py``): the program's published
    configuration (its small CPU variant where the file says
    ``program_reduced``, for tests) with the fields of ``model.overrides``
    set from the file. A width that differs between the program and the
    file (``model.check``), or a type other than the file states, is an
    error."""
    import jax.numpy as jnp
    from repro import configs as cfglib
    arch = cfglib.get(cfg["program_arch"])
    api = arch.api(reduced=bool(cfg.get("program_reduced", False)),
                   overrides=model.overrides(cfg))
    c = api.cfg
    have = {"compute_dtype": jnp.dtype(c.dtype).name,
            "param_dtype": jnp.dtype(c.param_dtype).name}
    wrong = {k: (v, cfg[k]) for k, v in have.items() if v != cfg[k]}
    if wrong:
        raise ValueError(f"program config differs from {cfg['name']}: "
                         f"(program, file) {wrong}")
    model.check(cfg, c)
    return arch, api


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks) if peaks else 0


@contextlib.contextmanager
def profiled(enabled: bool, chips: int, box: dict):
    """Profile the body when ``enabled``; on exit ``box["summary"]`` holds
    the reduced trace and ``box["window_s"]`` the traced window's length.
    The trace is written under ``$TMPDIR`` and deleted once read."""
    if not enabled:
        t0 = time.monotonic()
        yield
        box["window_s"] = time.monotonic() - t0
        return
    import jax
    from bench import tracing
    d = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(d)
        t0 = time.monotonic()
        yield
        box["window_s"] = time.monotonic() - t0
        jax.profiler.stop_trace()
        box["summary"] = tracing.reduce(tracing.find_xplane(d),
                                        box["window_s"], chips)
    finally:
        shutil.rmtree(d, ignore_errors=True)


class CompileCounter:
    """Counts programs lowered (compiled, or loaded from the cache) while
    active: the window should have none."""
    _registered = False
    _active = None

    def __init__(self):
        self.count = 0
        self.names = []

    def __enter__(self):
        import jax
        if not CompileCounter._registered:
            jax.monitoring.register_event_duration_secs_listener(
                CompileCounter._listen)
            CompileCounter._registered = True
        CompileCounter._active = self
        return self

    def __exit__(self, *exc):
        CompileCounter._active = None
        return False

    @staticmethod
    def _listen(event, duration, **kw):
        active = CompileCounter._active
        if active is not None and event.endswith(
                "jaxpr_to_mlir_module_duration"):
            active.count += 1
            active.names.append(str(kw.get("fun_name", "?")))
