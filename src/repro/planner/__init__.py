from .cost_model import HW, layer_costs, stage_graph_costs
from .placement import (PlacementPlan, plan_placement, tpu_mesh_topology,
                        tpu_slice_topology)
from .taskgraph import (ep_step_graph, model_stage_graph, pipeline_graph,
                        serving_query_graph)
