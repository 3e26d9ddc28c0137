from janusgraph_tpu.olap.programs.pagerank import PageRankProgram  # noqa: F401
from janusgraph_tpu.olap.programs.shortest_path import ShortestPathProgram  # noqa: F401
from janusgraph_tpu.olap.programs.connected_components import (  # noqa: F401
    ConnectedComponentsProgram,
)
from janusgraph_tpu.olap.programs.traversal_count import (  # noqa: F401
    TraversalCountProgram,
)
from janusgraph_tpu.olap.programs.peer_pressure import PeerPressureProgram  # noqa: F401
from janusgraph_tpu.olap.programs.cdlp import CDLPProgram  # noqa: F401
from janusgraph_tpu.olap.programs.lcc import LCCProgram  # noqa: F401
from janusgraph_tpu.olap.programs.betweenness import (  # noqa: F401
    BetweennessCentralityProgram,
)
from janusgraph_tpu.olap.programs.olap_traversal import (  # noqa: F401
    OLAPTraversalProgram,
    TraversalStep,
    steps_from_spec,
)
from janusgraph_tpu.olap.programs.degree import DegreeCountProgram  # noqa: F401
from janusgraph_tpu.olap.programs.gcn import GCNForwardProgram  # noqa: F401
from janusgraph_tpu.olap.programs.embedding import (  # noqa: F401
    EmbeddingUpdateProgram,
)
