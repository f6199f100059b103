"""The system under test, built from the benchmark's own data."""
from __future__ import annotations

from typing import Any, Dict, List

from benchlib.reference import Graph


def cluster_params(spec: Dict[str, Any]):
    """(rates, speeds) of a configuration's switched cluster."""
    return list(map(float, spec["rates"])), list(map(float, spec["speeds"]))


def topology(core, spec: Dict[str, Any]):
    rates, speeds = cluster_params(spec)
    return core.fully_switched_topology(len(rates), rates=rates,
                                        link_speeds=speeds)


def spg(core, g: Graph, name: str):
    return core.SPG(n=g.n, edges=list(g.edges), weights=g.weights.copy(),
                    tpl=dict(g.tpl), name=name)


def policy(core, spec: Dict[str, Any]):
    assert spec["name"] == "HVLB_CC_B", spec
    return core.HVLB_CC_B(alpha_max=spec["alpha_max"],
                          alpha_step=spec["alpha_step"])


def alpha_grid(spec: Dict[str, Any]) -> List[float]:
    """The policy's alpha grid, point for point as the policy defines
    it: ``k * step`` for ``k = 0 .. round(max / step)``."""
    n = int(round(spec["alpha_max"] / spec["alpha_step"]))
    return [k * spec["alpha_step"] for k in range(n + 1)]


def graphs(rng, c, spec: Dict[str, Any], count: int) -> List[Graph]:
    from benchlib.gen import random_graph

    return [random_graph(spec["n"], rng, c, max_in=spec["max_in"],
                         max_out=spec["max_out"], ccr=spec["ccr"])
            for _ in range(count)]
