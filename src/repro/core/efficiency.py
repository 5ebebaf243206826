"""Energy-efficiency metrics: the ``Submission`` record a ``PowerRun``
produces (Samples/Joule, per-domain efficiency) and the Server-scenario
capacity figures (QPS at the tail SLO, and per joule).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Submission:
    version: str
    workload: str
    scale: str                        # tiny | edge | datacenter
    system_id: str                    # hardware identity for isolation
    software_id: str
    samples_per_second: float
    avg_watts: float
    accuracy_target: Optional[float] = None
    # multi-domain submissions: average watts per measured power
    # domain (accelerator / dram / host / wall / pdu / pin); the
    # boundary domains are what avg_watts totals
    per_domain_watts: Optional[dict] = None

    @property
    def samples_per_joule(self) -> float:
        return self.samples_per_second / self.avg_watts

    @property
    def joules_per_sample(self) -> float:
        return self.avg_watts / self.samples_per_second

    def domain_samples_per_joule(self) -> dict:
        """Per-domain efficiency: what the throughput costs on each
        rail (the paper's per-component attribution view)."""
        if not self.per_domain_watts:
            return {}
        return {k: self.samples_per_second / w
                for k, w in self.per_domain_watts.items() if w > 0}


def max_sustainable_qps(points: list[tuple], *,
                        min_attainment: float = 0.99) -> float:
    """Max offered QPS whose tail-SLO attainment stays at or above
    ``min_attainment`` — the Server-scenario capacity figure.

    Args:
        points: ``(qps, attainment)`` pairs from a QPS sweep — offered
            queries/s vs the fraction meeting the TTFT/TPOT tail SLOs
            (``ServerMetrics.tail_attainment``), any order.
        min_attainment: the attainment bar (fraction in [0, 1]; the
            paper-style default demands 99 %).

    Returns the highest sustaining QPS, or ``0.0`` when no swept point
    sustains the bar.  The sweep's grid sets the resolution; this does
    not interpolate between points (a knee between grid points reports
    the last *measured* sustaining rate).
    """
    ok = [float(q) for q, a in points
          if not np.isnan(a) and a >= min_attainment]
    return max(ok, default=0.0)


def qps_at_slo_per_joule(qps_at_slo: float, avg_watts: float) -> float:
    """Max sustainable QPS at the tail SLO per joule: queries/s of
    SLO-compliant capacity per watt of measured draw — equivalently,
    SLO-compliant queries per joule (1/s / W == 1/J).  The Server
    energy-efficiency headline the SLO sweep reports.

    Args:
        qps_at_slo: ``max_sustainable_qps`` output (queries/s).
        avg_watts: mean measured system draw over the sustaining run
            (boundary-channel watts — wall, or pdu for fleets).
    """
    if avg_watts <= 0:
        return 0.0
    return qps_at_slo / avg_watts
