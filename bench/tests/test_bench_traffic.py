"""The traffic load draws the same data from one seed, and other data
from another."""
import json
import os

import numpy as np

from benchlib import reference as R
from loads import program

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def _submit_pool(seed):
    cfg = _load("configs", "tgff-p16-n300")
    cfg["graphs"]["n"] = 30                   # the draws, not the size
    rates, speeds = program.cluster_params(cfg["cluster"])
    c = R.switched_cluster(rates, speeds)
    rng = np.random.default_rng(seed % 2**64)
    pool = program.graphs(rng, c, cfg["graphs"],
                          _load("traffic", "submit")["pool"])
    return [(g.edges, g.weights.tolist(), sorted(g.tpl.items()))
            for g in pool]


def test_submit_pool_is_a_function_of_the_seed():
    big = 2**31 + 12345
    assert _submit_pool(big) == _submit_pool(big)
    assert _submit_pool(big) != _submit_pool(big + 1)


def test_cluster_of_the_submit_configuration_is_fixed():
    cfg = _load("configs", "tgff-p16-n300")
    rates, speeds = program.cluster_params(cfg["cluster"])
    assert len(rates) == len(speeds) == cfg["cluster"]["procs"]
    assert set(rates) == {1.0, 0.67, 0.83}      # the paper's rates
    assert set(speeds) == {1.0, 3.0}            # Fig. 2's link speeds


def test_full_size_graph_meets_the_caps():
    cfg = _load("configs", "tgff-p16-n300")
    c = R.switched_cluster(*program.cluster_params(cfg["cluster"]))
    rng = np.random.default_rng((2**31 + 4242) % 2**64)
    g, = program.graphs(rng, c, cfg["graphs"], 1)
    assert g.n == cfg["graphs"]["n"]
    assert max(len(p) for p in g.pred) <= cfg["graphs"]["max_in"]
    assert max(len(s) for s in g.succ) <= cfg["graphs"]["max_out"]
    assert sum(1 for p in g.pred if not p) >= 2
    assert sum(1 for s in g.succ if not s) >= 2
