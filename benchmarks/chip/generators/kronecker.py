"""kronecker: the Graph500 / GAP ``kron`` graph, RMAT draws of the
configuration's initiator (``graphgen.graph_edges``)."""
import graphgen


def edges(graph: dict):
    return graphgen.graph_edges(graph["scale"], graph["edge_factor"], graph["initiator"],
                                graph["seed"])
