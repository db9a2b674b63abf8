"""Operation and byte counts the per-layer metrics divide by, and the
chip's peaks.

Kept with the benchmark so that a program change cannot move its own
yardstick: a change to a kernel changes the program's time, and only a
benchmark change changes these counts. Every count is from the shapes of a
configuration file (``bench/configs/<name>.json``), never from the program.
The counts that depend on the model family are its module's
(``bench/models/<model>.py``: ``train_step_flops`` and what it needs);
this module holds the conventions they follow and the family-free parts.

Conventions:

* A matmul with K x N weights costs 2 K N operations per token forward, and
  twice that backward (input and weight gradients): 6 per parameter per
  token for a training step. Only the experts a token is routed to count.
* The embedding lookup is a gather, not a matmul: not counted. The output
  head is a matmul: counted.
* Causal attention: the scores cost 2 x (q.k width) and the weighted sum
  2 x (v width) operations per head and (query, key) pair a query attends
  to (``attended_pairs``). A sliding window caps the keys a query sees.
  Recomputation (remat) is not counted.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: pathlib.Path = PEAKS) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``. A kind that is not in
    the table is an error, not a default."""
    table = json.loads(pathlib.Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"{path} has {sorted(table)}")
    return table[device_kind]


def attended_pairs(seq: int, window) -> int:
    """(query, key) pairs of one causal sequence of ``seq`` tokens, with
    each query seeing at most ``window`` keys (itself included)."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    w = window
    return w * (w + 1) // 2 + (seq - w) * w


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: Dict[str, float]):
    """Least time over measured time, as a percentage, and which bound sets
    the least time (``"flops"`` or ``"bytes"``)."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
