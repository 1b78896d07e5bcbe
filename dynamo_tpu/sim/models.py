"""Device models driving simulated worker timing.

Decode step times are a fixed base + per-sequence line (the constants
below), TP collective overhead from :mod:`dynamo_tpu.parallel.ici_model`
(``tp_decode_step_s``), pp boundary cost from ``pp_boundary_s``, and KV
transfer time from the SAME ``LinkStats``/``AdmissionGate`` classes the
live fabric uses (llm/kv/fabric.py) — the simulator prices a fetch with
the exact arithmetic the production gate runs.
"""

from __future__ import annotations

import dataclasses

from ..parallel import ici_model

__all__ = ["WorkerPerfModel"]

# Decode step-time line for a Llama-8B-class replica (17.6 ms at batch 32,
# 32.7 ms at batch 128). Constants of the simulator, read from no record
# file; not measured on this round's chip (PERF.md).
_DEFAULT_BASE_S = 0.0126
_DEFAULT_SLOPE_S = 0.000157


@dataclasses.dataclass
class WorkerPerfModel:
    """One simulated replica's timing truth.

    ``step_time_s(batch)`` is the decode dispatch time with ``batch``
    concurrent sequences (continuous batching: every active sequence
    advances one token per step). TP adds the modeled ICI collective
    cost, pp adds the DCN boundary hops — both from parallel/ici_model.
    """

    prefill_tok_per_s: float = 4000.0
    step_base_s: float = _DEFAULT_BASE_S
    step_per_seq_s: float = _DEFAULT_SLOPE_S
    tp: int = 1
    pp: int = 1
    hidden: int = 4096
    num_layers: int = 32
    kv_bytes_per_block: int = 1 << 20

    def step_time_s(self, batch: int) -> float:
        b = max(int(batch), 1)
        t = self.step_base_s + self.step_per_seq_s * b
        if self.tp > 1:
            t += ici_model.tp_decode_step_s(b, self.hidden, self.num_layers,
                                            self.tp)
        if self.pp > 1:
            t += self.pp * ici_model.pp_boundary_s(b, self.hidden, self.pp)
        return t

    def prefill_s(self, tokens: int) -> float:
        if tokens <= 0:
            return 0.0
        return tokens / self.prefill_tok_per_s
