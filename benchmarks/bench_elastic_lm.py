"""Beyond-paper: elastic LM-state rescale via CEP vs hash-sharded restore.

Plans the k→k±1 reshard of a full qwen2-1.5b checkpoint (params + optimizer
moments) and reports bytes moved — then *executes* each rescale with
ElasticRescaler on a block-proxy pack (each packed row stands for a fixed-size
block of the flattened checkpoint) so the serving scenario reports executed,
not just planned, migration bytes and the on-device program latency.
Also exercises MoE expert-placement rescale.
"""
from __future__ import annotations

import numpy as np

from repro import configs
from repro.elastic import expert_place as ep
from repro.elastic import resharder as rs
from repro.elastic.rescale_exec import ElasticRescaler
from repro.graphs import engine as E
from repro.launch import mesh as MM

from .common import emit

PROXY_ROWS = 1 << 17  # checkpoint blocks packed as rescaler rows (≫ k², so
# the row-granularity CEP plan tracks the element-exact moved fraction)


def _executed_stats(rescaler: ElasticRescaler, k_old: int, k_new: int):
    """Execute the k_old→k_new rescale on the block-proxy pack. Row ids are
    synthetic (the rescaler moves ranges, never reads endpoints); recheck is
    skipped — graph quality metrics are meaningless for checkpoint blocks."""
    ids = np.zeros(PROXY_ROWS, dtype=np.int64)
    data = E.pack_ordered_sharded(ids, ids, 1, k_old, MM.make_graph_mesh(1))
    return rescaler.execute(data, rescaler.plan(data, k_new), recheck=False)[1]


def run() -> None:
    cfg = configs.get_config("qwen2-1.5b")
    n = cfg.param_count()
    shapes = {
        "params_bf16": ((n,), 2),
        "adam_m_f32": ((n,), 4),
        "adam_v_f32": ((n,), 4),
    }
    rescaler = ElasticRescaler()
    for k_old, k_new in [(16, 17), (16, 15), (256, 257), (16, 32)]:
        plan = rs.plan_reshard(shapes, k_old, k_new)
        s = plan.summary()
        stats = _executed_stats(rescaler, k_old, k_new)
        # Each executed row stands for total_bytes/PROXY_ROWS checkpoint bytes.
        executed_frac = stats.migrated_edges / stats.num_edges
        executed_bytes = executed_frac * s["total_bytes"]
        emit(
            f"elastic/reshard_{k_old}to{k_new}", stats.elapsed_s * 1e6,
            f"moved_GB={s['moved_bytes']/1e9:.2f};moved_frac={s['moved_frac']:.3f};"
            f"executed_GB={executed_bytes/1e9:.2f};executed_frac={executed_frac:.3f};"
            f"executed_ops={stats.copy_ops};hash_frac={s['random_frac']:.3f}",
        )
        # Block granularity only rounds at chunk boundaries: the executed
        # fraction must track the element-exact plan to within a couple of
        # rows per overlay boundary (≤ k_old + k_new of them).
        slack = 2 * (k_old + k_new) / PROXY_ROWS
        assert abs(executed_frac - s["moved_frac"]) <= slack + 1e-9, (
            executed_frac, s["moved_frac"])
    # MoE expert placement: co-activation-aware EP groups + elastic resize.
    rng = np.random.default_rng(0)
    e = 64
    stats = rng.random((e, e))
    for c in range(0, e, 8):  # 8 co-activation communities
        stats[c : c + 8, c : c + 8] += 4.0
    stats = (stats + stats.T) / 2
    np.fill_diagonal(stats, 0)
    order = ep.order_experts(stats)
    placed = ep.ExpertPlacement(order, 8)
    naive = ep.ExpertPlacement(np.arange(e), 8)
    rng2 = np.random.default_rng(1)
    shuf = ep.ExpertPlacement(rng2.permutation(e), 8)
    emit(
        "elastic/expert_traffic", 0.0,
        f"geo={ep.cross_group_traffic(stats, placed):.0f};"
        f"default={ep.cross_group_traffic(stats, naive):.0f};"
        f"shuffled={ep.cross_group_traffic(stats, shuf):.0f}",
    )
    _, moved = placed.rescale(9)
    emit("elastic/expert_rescale_8to9", 0.0, f"experts_moved={moved}/64")


if __name__ == "__main__":
    run()
