"""uniform: the GAP ``urand`` graph, every edge uniform, drawn as RMAT draws
of the uniform initiator 0.25/0.25/0.25 (``graphgen.graph_edges``)."""
import graphgen


def edges(graph: dict):
    return graphgen.graph_edges(graph["scale"], graph["edge_factor"], graph["initiator"],
                                graph["seed"])
