"""Metrics / observability (SURVEY §5.5 upgrade).

The reference logs an unreduced per-rank loss via print every 100 batches
(/root/reference/mingpt/trainer.py:144-147) and nothing else; its README
self-deprecates the approach (README.md:74). Here: structured per-step
metrics — loss (already a global mean: the batch axis spans the whole mesh),
grad norm, LR, tokens/sec/chip and MFU from the 6ND flop model — emitted from
process 0 only, to stdout and optionally a JSONL file (pluggable sink).

ISSUE 5: the roofline peak tables, the ``RateWindow`` helper, and the
JSONL schema now live in ``mingpt_distributed_tpu.telemetry`` (re-exported
here for back-compat), and every scalar the logger prints is also set on
``mingpt_train_*`` gauges in a :class:`~..telemetry.MetricsRegistry` —
pass the process registry (``telemetry.get_registry()``) to expose them
on the same ``/metrics`` page as the serving metrics.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional

from mingpt_distributed_tpu.config import GPTConfig
from mingpt_distributed_tpu.telemetry import (  # noqa: F401 — re-exports
    PEAK_FLOPS,
    PEAK_HBM_BYTES,
    JsonlEventSink,
    MetricsRegistry,
    RateWindow,
    log_event,
    peak_flops_per_chip,
    peak_hbm_bytes_per_chip,
)

_GAUGE_SAFE_RE = re.compile(r"[^a-zA-Z0-9_]")


def flops_per_token(cfg: GPTConfig, seq_len: Optional[int] = None) -> float:
    """Training FLOPs/token: 6*N_matmul + attention term 12*L*d*T
    (the 6ND model with the quadratic-attention correction)."""
    t = seq_len or cfg.block_size
    d, l, v = cfg.n_embd, cfg.n_layer, cfg.vocab_size
    ffn = cfg.dense_width
    kv = cfg.kv_heads * cfg.head_dim
    per_layer = d * (d + 2 * kv) + d * d  # qkv + out proj
    per_layer += (3 if cfg.swiglu else 2) * d * ffn
    n_matmul = l * per_layer + d * v  # + lm head (embeddings are gathers)
    attn = 12 * l * d * t  # 2 score+value matmuls, fwd+bwd (6x), * d * T
    return 6 * n_matmul + attn


class MetricsLogger:
    """stdout + optional JSONL + optional TensorBoard sinks + registry
    gauges; rate/MFU computed over log windows (SURVEY §5.5's prescription
    — the reference logs per-rank unreduced loss via print only,
    trainer.py:144-147).

    ``registry`` defaults to a fresh private one (test isolation, the
    prometheus_client idiom); entry points pass
    ``telemetry.get_registry()`` so training gauges land on the shared
    scrape page. The JSONL sink writes the versioned
    ``mingpt-telemetry/1`` schema with ``kind: "train_step"`` and the
    per-step scalars flat at the top level (pre-existing consumers that
    read ``rec["loss"]``/``rec["step"]`` are unaffected).
    """

    def __init__(
        self,
        cfg: GPTConfig,
        *,
        jsonl_path: Optional[str] = None,
        tensorboard_dir: Optional[str] = None,
        n_chips: int = 1,
        enabled: bool = True,
        registry: Optional[MetricsRegistry] = None,
    ):
        self.cfg = cfg
        self.n_chips = max(n_chips, 1)
        self.enabled = enabled
        self.registry = registry if registry is not None else MetricsRegistry()
        self._jsonl: Optional[JsonlEventSink] = None
        if enabled and jsonl_path:
            self._jsonl = JsonlEventSink(jsonl_path)
        self._tb = None
        if enabled and tensorboard_dir:
            try:
                from torch.utils.tensorboard import SummaryWriter

                self._tb = SummaryWriter(log_dir=tensorboard_dir)
            except Exception as e:  # optional dep — degrade to other sinks
                log_event(f"tensorboard sink unavailable ({e}); continuing")
        self._rate = RateWindow()
        self._peak = peak_flops_per_chip()
        self._step_gauge = self.registry.gauge(
            "mingpt_train_step", help="last logged training step")
        self._gauges: Dict[str, Any] = {}
        # Pre-register the headline families so the scrape page advertises
        # them from process start — MFU in particular may never be observed
        # on chips missing from the peak table (e.g. the CPU test mesh).
        for key, help_ in (
            ("loss", "training loss (global mean over the mesh batch axis)"),
            ("mfu", "model FLOPs utilization vs the chip's roofline peak"),
        ):
            self._gauges[key] = self.registry.gauge(
                f"mingpt_train_{key}", help=help_)

    def _gauge(self, key: str):
        g = self._gauges.get(key)
        if g is None:
            safe = _GAUGE_SAFE_RE.sub("_", key)
            g = self.registry.gauge(
                f"mingpt_train_{safe}", help=f"training scalar {key!r}")
            self._gauges[key] = g
        return g

    def log_step(
        self, step: int, tokens_per_step: int, seq_len: int, scalars: Dict[str, Any]
    ) -> Dict[str, Any]:
        rec: Dict[str, Any] = {"step": step}
        rec.update({k: float(v) for k, v in scalars.items()})
        steps_per_sec = self._rate.observe(step)
        if steps_per_sec is not None:
            tps = tokens_per_step * steps_per_sec
            rec["tokens_per_sec"] = tps
            rec["tokens_per_sec_per_chip"] = tps / self.n_chips
            flops = flops_per_token(self.cfg, seq_len) * tps / self.n_chips
            rec["flops_per_chip"] = flops
            if self._peak:
                rec["mfu"] = flops / self._peak
        self._step_gauge.set(step)
        for k, v in rec.items():
            if k != "step":
                self._gauge(k).set(v)
        if self.enabled:
            parts = [f"step {step}"] + [
                f"{k} {v:.4g}" for k, v in rec.items() if k != "step"
            ]
            log_event(" | ".join(parts), step=step)
            if self._jsonl:
                self._jsonl.write("train_step", dict(rec))
            if self._tb:
                for k, v in rec.items():
                    if k != "step":
                        self._tb.add_scalar(k, v, step)
        return rec

    def close(self) -> None:
        if self._jsonl:
            self._jsonl.close()
            self._jsonl = None
        if self._tb:
            self._tb.close()
            self._tb = None
