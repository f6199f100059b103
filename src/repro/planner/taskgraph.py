"""SPG construction from model configs and serving-query sets.

``model_stage_graph`` — the training/serving pipeline of one model as a
chain SPG (embed -> stage units -> head).

``serving_query_graph`` — the automotive-DSMS analogue: several registered
queries (applications) consume shared backbone outputs; sharing creates
high-out-degree hub nodes at depth > 1, exactly the SPG family (Section
3.2) that breaks HSV_CC ordering and motivates HVLB_CC (B).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from repro import tracing
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.graph import SPG
from repro.core.topology import Topology

from .cost_model import (ep_attention_flops, ep_expert_flops,
                         ep_expert_weight_bytes, ep_kv_bytes,
                         ep_message_bytes, stage_graph_costs)


def model_stage_graph(cfg: ModelConfig, shape: ShapeConfig,
                      n_stage_units: int = 16) -> SPG:
    """Chain SPG: embed -> unit_1 .. unit_k -> head.

    Node weights are FLOPs (so ``comp = w / mu`` with mu in FLOP/s yields
    seconds); edge volumes are boundary activation bytes.
    """
    units, act_bytes = stage_graph_costs(cfg, shape, n_stage_units)
    from .cost_model import layer_costs
    c = layer_costs(cfg, shape)
    weights = [c["embed"]] + units + [c["head"]]
    n = len(weights)
    edges = [(i, i + 1) for i in range(n - 1)]
    g = SPG(n=n, edges=edges, weights=np.asarray(weights),
            name=f"{cfg.name}-{shape.name}")
    for e in edges:
        g.tpl[e] = float(act_bytes)
    return g


def pipeline_graph(cfg: ModelConfig, shape: ShapeConfig,
                   n_microbatches: int = 8,
                   n_stage_units: int = 16) -> SPG:
    """M parallel microbatch chains (each 1/M of the tokens).

    List-scheduling this DAG is pipeline-schedule synthesis: processor
    contention serializes stages on a slice while independent microbatches
    overlap — the GPipe bubble appears as schedule holes (which
    HVLB_CC_IC can fill with optional work, Section 4.4).
    """
    units, act_bytes = stage_graph_costs(cfg, shape, n_stage_units)
    from .cost_model import layer_costs
    c = layer_costs(cfg, shape)
    chain = [c["embed"]] + units + [c["head"]]
    chain = [w / n_microbatches for w in chain]
    act = act_bytes / n_microbatches
    k = len(chain)
    weights: List[float] = []
    edges: List[Tuple[int, int]] = []
    for m in range(n_microbatches):
        base = m * k
        weights.extend(chain)
        edges.extend((base + i, base + i + 1) for i in range(k - 1))
    g = SPG(n=len(weights), edges=edges, weights=np.asarray(weights),
            name=f"{cfg.name}-pipe{n_microbatches}x{k}")
    for e in edges:
        g.tpl[e] = float(act)
    return g


def serving_query_graph(cfg: ModelConfig, shape: ShapeConfig,
                        n_queries: int = 3,
                        n_stage_units: int = 8) -> SPG:
    """Backbone + per-query operator subgraphs (the DSMS workload).

    Each registered query taps the backbone output (and optionally an
    intermediate stage), runs 2-3 post-processing operators (filter /
    map / join analogues as FLOP-weighted tasks) and ends in an
    application sink.  The backbone output node acquires out-degree
    ``n_queries`` > its predecessors' out-degree — the stream-processing
    shape of the paper.

    The returned SPG carries ``query_ops``: query index -> the node ids
    of its ``(op1, op2, sink)`` operators.  Consumers (``serve.DSMSEngine``)
    must use this mapping instead of recomputing node positions from the
    graph size, so graph-shape changes cannot silently misattribute
    schedule holes.
    """
    base = model_stage_graph(cfg, shape, n_stage_units)
    weights: List[float] = list(base.weights)
    edges: List[Tuple[int, int]] = list(base.edges)
    tpl: Dict[Tuple[int, int], float] = dict(base.tpl)
    act = tpl[base.edges[0]]
    hub = base.n - 1                      # head output feeds every query
    query_ops: Dict[int, Tuple[int, int, int]] = {}
    rng = np.random.default_rng(0)
    for q in range(n_queries):
        # operator 1 (filter/map) <- hub
        op1 = len(weights)
        weights.append(float(weights[hub]) * 0.05 * (1 + q % 3))
        edges.append((hub, op1))
        tpl[(hub, op1)] = act * 0.1
        # operator 2 (join with an intermediate tap every other query)
        op2 = len(weights)
        weights.append(float(weights[hub]) * 0.02)
        edges.append((op1, op2))
        tpl[(op1, op2)] = act * 0.05
        if q % 2 == 1:
            tap = 1 + (q % (base.n - 2))
            edges.append((tap, op2))
            tpl[(tap, op2)] = act * 0.05
        # sink application
        sink = len(weights)
        weights.append(float(weights[hub]) * 0.01)
        edges.append((op2, sink))
        tpl[(op2, sink)] = act * 0.01
        query_ops[q] = (op1, op2, sink)
    g = SPG(n=len(weights), edges=edges, weights=np.asarray(weights),
            name=f"{cfg.name}-dsms-{n_queries}q")
    g.tpl.update(tpl)
    g.query_ops = query_ops
    return g


@tracing.traced("repro.planner.ep_graph")
def ep_step_graph(cfg: ModelConfig, tokens: np.ndarray, kv_len: np.ndarray,
                  n_microbatches: int, tg: Topology) -> SPG:
    """One prefill step of an MoE model under expert parallelism on the
    chips of ``tg``.

    ``tokens[l, m, d, e]`` is the number of tokens of data-parallel rank
    ``d``'s chunk in microbatch ``m`` that layer ``l``'s router sends to
    expert ``e`` (each token goes to ``cfg.top_k`` experts, so a chunk's
    row sums to ``chunk * top_k``); ``kv_len[m, d]`` is the keys that
    chunk attends to (its prompt offset plus the chunk).  The layers may
    be the first ``L <= cfg.n_layers`` of the model.

    Per layer ``l`` and microbatch ``m``: one attention task per rank
    (attention of the chunk against ``kv_len`` keys, plus the router;
    ``cost_model.ep_attention_flops``) and one task per expert (its MLP
    over the tokens routed to it; ``ep_expert_flops``).  Edge
    ``attn(l, m, d) -> expert(l, m, e)`` carries the dispatch of
    ``tokens[l, m, d, e]`` activations, edge ``expert(l, m, e) ->
    attn(l + 1, m, d)`` their combine (``ep_message_bytes``, bf16).
    Weights are FLOPs, volumes bytes.  Every edge is present, with
    volume 0 where no token is routed.

    Residency: rank ``d``'s KV cache lives on chip ``d mod P`` and
    expert ``e``'s weights on chip ``e mod P`` (one rank and one expert
    a chip when both number ``P``).  A task runs on its home chip in
    ``FLOPs / rate``; on any other chip ``p`` it also streams what it
    needs from home, ``bytes / tg.route_speed(home, p)``: an attention
    task its layer's KV cache (``ep_kv_bytes`` of ``kv_len``), an expert
    task its weights (``ep_expert_weight_bytes``).  That time is the
    executing chip's; it takes no link in the contention model.  So the
    graph carries ``comp_matrix`` (tasks x chips), fixed to ``tg``'s
    rates: to place the step with other rates, build it again on that
    topology.

    The returned SPG carries ``ep_tasks``: task id -> ``(layer,
    microbatch, kind, index)``, kind ``"attn"`` (index: the rank) or
    ``"expert"`` (index: the expert), and ``ep_home``: each task's home
    chip.  Task ids run layer-major, then microbatch, then the ranks'
    attention tasks, then the experts.
    """
    tokens = np.asarray(tokens)
    kv_len = np.asarray(kv_len)
    if tokens.ndim != 4:
        raise ValueError(f"tokens must be (layers, microbatches, ranks, "
                         f"experts), got shape {tokens.shape}")
    L, M, D, E = tokens.shape
    if M != n_microbatches:
        raise ValueError(f"tokens hold {M} microbatches, expected "
                         f"{n_microbatches}")
    if E != cfg.n_experts or not 0 < L <= cfg.n_layers:
        raise ValueError(f"tokens of {L} layers x {E} experts do not fit "
                         f"{cfg.name} ({cfg.n_layers} layers, "
                         f"{cfg.n_experts} experts)")
    if kv_len.shape != (M, D):
        raise ValueError(f"kv_len must have shape {(M, D)}, got "
                         f"{kv_len.shape}")
    if not np.issubdtype(tokens.dtype, np.integer) or (tokens < 0).any():
        raise ValueError("tokens must be non-negative integers")
    routed = tokens.sum(axis=3)
    chunk, rem = divmod(int(routed.flat[0]), cfg.top_k)
    if rem or (routed != chunk * cfg.top_k).any():
        raise ValueError(f"every chunk must route the same number of "
                         f"tokens to {cfg.top_k} experts each")
    if (kv_len < chunk).any():
        raise ValueError(f"kv_len must be at least the chunk ({chunk})")

    def attn(l: int, m: int, d: int) -> int:
        return (l * M + m) * (D + E) + d

    def expert(l: int, m: int, e: int) -> int:
        return (l * M + m) * (D + E) + D + e

    P = tg.n_procs
    n = L * M * (D + E)
    weights = np.zeros(n)
    resident = np.zeros(n)          # bytes a task streams in off home
    home = np.zeros(n, dtype=np.int64)
    tpl: Dict[Tuple[int, int], float] = {}
    ep_tasks: Dict[int, Tuple[int, int, str, int]] = {}
    w_bytes = ep_expert_weight_bytes(cfg)
    for l in range(L):
        for m in range(M):
            for d in range(D):
                t = attn(l, m, d)
                weights[t] = ep_attention_flops(cfg, chunk,
                                                int(kv_len[m, d]))
                resident[t] = ep_kv_bytes(cfg, int(kv_len[m, d]))
                home[t] = d % P
                ep_tasks[t] = (l, m, "attn", d)
            for e in range(E):
                t = expert(l, m, e)
                weights[t] = ep_expert_flops(cfg,
                                             int(tokens[l, m, :, e].sum()))
                resident[t] = w_bytes
                home[t] = e % P
                ep_tasks[t] = (l, m, "expert", e)
                for d in range(D):
                    vol = ep_message_bytes(cfg, int(tokens[l, m, d, e]))
                    tpl[(attn(l, m, d), t)] = vol
                    if l + 1 < L:
                        tpl[(t, attn(l + 1, m, d))] = vol
    # bytes/s from chip h to chip p; the diagonal is never read
    speed = np.ones((P, P))
    for h in range(P):
        for p in range(P):
            if p != h:
                speed[h, p] = tg.route_speed(h, p)
    away = np.where(home[:, None] == np.arange(P)[None, :], 0.0,
                    resident[:, None] / speed[home])
    comp = weights[:, None] / np.asarray(tg.rates, float)[None, :] + away
    g = SPG(n=n, edges=list(tpl), weights=weights, tpl=tpl,
            comp_matrix=comp, name=f"{cfg.name}-ep{E}-{L}L{M}mb")
    g.ep_tasks = ep_tasks
    g.ep_home = home
    return g
