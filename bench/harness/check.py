"""The comparison that decides ``correct``: what the timed calls produced
against the plain reference, each number beside its limit.

Numbers compared (each the worst over all cells of the call):

* ``tables_power``  relative gap of the section V table power [P, T, M];
* ``tables_volt``   absolute gap of the selected rail voltages (V);
* ``power``         relative gap of mean power (campaign: per cell;
  composition: per candidate and scenario, summed over sub-fleets);
* ``qos``/``served`` absolute gaps of the QoS-violation rate and the
  served fraction (composition: capacity-weighted over sub-fleets);
* ``backlog``/``mispred`` absolute gaps of mean backlog and the
  misprediction rate (campaign);
* ``tenant_qos``/``tenant_served`` absolute gaps per tenant (tenant
  planes).
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-12)))


def _abs(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)))


def host_tables(tables) -> Dict[str, np.ndarray]:
    """The spied ``BinTables`` of a call as host arrays."""
    return {f: np.asarray(getattr(tables, f), np.float64)
            for f in ("power", "v_core", "v_bram")}


def campaign_stats(cfg: dict, out: dict, scen_names: Sequence[str],
                   tenants: bool) -> Dict[str, np.ndarray]:
    """A ``run_campaign`` result as arrays in the reference's cell order."""
    keys = [("fpga:" + p, t, s) for p in cfg["platforms"]
            for t in cfg["techniques"] for s in scen_names]
    tab = out["table"]
    fields = ["mean_power_w", "qos_violation_rate", "served_fraction",
              "mean_backlog", "misprediction_rate"]
    if tenants:
        fields += ["tenant_qos_violation_rate", "tenant_served_fraction"]
    return {f: np.asarray([tab[p][t][s][f] for p, t, s in keys], np.float64)
            for f in fields}


def gaps(kind: str, prog: Dict[str, np.ndarray], ptab: Dict[str, np.ndarray],
         ref: dict) -> Dict[str, float]:
    rt = ref["tables"]
    out = {"tables_power": _rel(ptab["power"], rt["power"]),
           "tables_volt": max(_abs(ptab["v_core"], rt["v_core"]),
                              _abs(ptab["v_bram"], rt["v_bram"]))}
    if kind == "composition":
        out["power"] = _rel(prog["total_power_w"], ref["total_power_w"])
        out["qos"] = _abs(prog["qos_violation_rate"],
                          ref["qos_violation_rate"])
        out["served"] = _abs(prog["served_fraction"], ref["served_fraction"])
        return out
    out["power"] = _rel(prog["mean_power_w"], ref["mean_power_w"])
    out["qos"] = _abs(prog["qos_violation_rate"], ref["qos_violation_rate"])
    out["served"] = _abs(prog["served_fraction"], ref["served_fraction"])
    out["backlog"] = _abs(prog["mean_backlog"], ref["mean_backlog"])
    out["mispred"] = _abs(prog["misprediction_rate"],
                          ref["misprediction_rate"])
    if "tenant_qos_violation_rate" in prog:
        act = ref["active"]
        out["tenant_qos"] = _abs(
            prog["tenant_qos_violation_rate"][act],
            ref["tenant_qos_violation_rate"][act])
        out["tenant_served"] = _abs(
            prog["tenant_served_fraction"][act],
            ref["tenant_served_fraction"][act])
    return out


def worst(per_call: List[Dict[str, float]]) -> Dict[str, float]:
    """The worst reading of each number over all calls."""
    return {k: max(g[k] for g in per_call) for k in per_call[0]}


def judge(readings: Dict[str, float], limits: Dict[str, float]
          ) -> Dict[str, dict]:
    """``{number: {"value", "limit", "ok"}}``; a number without a limit
    fails, so a cell cannot pass on a number nobody bounded."""
    out = {}
    for k, v in readings.items():
        lim = limits.get(k)
        out[k] = {"value": v, "limit": lim,
                  "ok": lim is not None and np.isfinite(v) and v <= lim}
    return out
