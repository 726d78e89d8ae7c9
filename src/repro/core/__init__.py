"""3D-TrIM core: conv planning, dataflow simulator, analytical models,
tiling, roofline."""

from repro.core.conv_plan import (  # noqa: F401
    ConvPlan, Conv1dPlan, DepthwisePlan, WeightGradPlan,
    input_grad_geometry,
    slice_reads_per_channel,
)
from repro.core.conv_shard import (  # noqa: F401
    ShardedConvPlan, resolve_conv_mesh,
)
from repro.core.netplan import (  # noqa: F401
    EdgeState, JoinStep, LayerStep, NetworkGraph, NetworkPlan,
    PoolInferenceError, graph_nodes, infer_pools, linear_graph_nodes,
    network_layers, scale_graph, scale_layers,
)
from repro.core.fuse_plan import (  # noqa: F401
    FusedGroup, FusedGroupPlan, FusedStage, GraphFusePlan, build_group,
    graph_segments,
)
from repro.core.model import (  # noqa: F401
    ConvLayer, GraphNode, HWConfig, TRIM, TRIM_3D,
    ifmap_reads_per_channel, ifmap_overhead_pct, fig1_curve,
    layer_accesses, compare_layer, fig6, vgg16_layers, alexnet_layers,
    mobilenet_layers, mobilenet_v1_layers, resnet18_graph, unet_graph,
)
from repro.core.dataflow import (  # noqa: F401
    TrimSliceSim, SliceStats, core_conv, reference_conv2d_valid,
)
from repro.core.tiling import (  # noqa: F401
    subkernel_decomposition,
)
from repro.core.serving import (  # noqa: F401
    BucketGrid, QueueFull, Replica, ServingEngine, pow2_buckets, replay,
)
