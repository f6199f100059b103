"""The roofline work function, counted by hand on the paper's example."""
import pytest

from benchlib import roofline
from benchlib.reference import Cluster, Graph

# Fig. 3 of arXiv 1705.00307: 10 tasks, 13 edges; n9 and n10 are exits
EDGES = [(0, 3), (0, 4), (1, 3), (1, 4), (2, 4), (2, 5), (3, 6), (3, 7),
         (4, 6), (4, 7), (5, 8), (6, 9), (7, 8)]
# Fig. 2: routes p1-p2 (2 and 3 hops), p1-p3 (2 and 3), p2-p3 (2 and 1)
ROUTES = {(0, 1): [("l1", "l2"), ("l1", "l4", "l3")],
          (0, 2): [("l1", "l4"), ("l1", "l2", "l3")],
          (1, 2): [("l2", "l4"), ("l3",)]}


def paper():
    routes = dict(ROUTES)
    for (a, b), rr in ROUTES.items():
        routes[(b, a)] = [tuple(reversed(r)) for r in rr]
    c = Cluster([0.67, 1.0, 0.83],
                {"l1": 1.0, "l2": 1.0, "l3": 3.0, "l4": 1.0}, routes,
                [1.0, 1.5, 1.5])
    return Graph(10, EDGES, [1.0] * 10, {e: 1.0 for e in EDGES}), c


def test_work_by_hand():
    g, c = paper()
    # a message's route walk, mean over the 6 ordered pairs: a route of
    # h hops costs 4h + 1; pairs p1-p2 and p1-p3 walk 9 + 13, p2-p3 9 + 5
    route = (22 + 22 + 14) * 2 / 6
    # per candidate: 13 incoming edges in all, each route + 1; per task
    # 2 (EST, EFT) + 1 (argmin), + 2 (value) for the 8 non-exits
    per_candidate = 13 * (route + 1) + 10 * 3 + 8 * 2
    ops = 3 * per_candidate + 10 * 4          # 3 candidates; load update
    assert ops == pytest.approx(971.0)
    got_ops, got_bytes = roofline.work(g, c, n_alphas=5)
    assert got_ops == pytest.approx(5 * 971.0)
    # inputs once: comp + LDET (10 x 3 each), 13 edges, 4 links; per
    # alpha 3 values of 10 tasks; 4 bytes each
    assert got_bytes == 4 * (60 + 13 + 4) + 5 * 4 * 30


def test_least_time_names_its_bound():
    t, bound = roofline.least_time(197e12, 1.0, "TPU v5 lite")
    assert (t, bound) == (pytest.approx(1.0), "flops")
    t, bound = roofline.least_time(1.0, 819e9, "TPU v5 lite")
    assert (t, bound) == (pytest.approx(1.0), "bytes")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.least_time(1.0, 1.0, "TPU v99")
