"""Checkpoint store (checkpoint/store.py): the on-disk CEP-chunk layout
contract, round-tripping the streaming pack_slots layout plus the orderer's
slot state, resharded (k → k') restore, and the Thm.-2 bytes-touched
accounting — the checkpoint path the out-of-core pipeline leans on when a
preempted host's replacement pulls only its own chunk."""
import json

import numpy as np
import pytest

from repro.checkpoint import store
from repro.core import cep, ordering
from repro.core.graph import rmat_graph
from repro.graphs import engine as GE
from repro.stream import IncrementalOrderer, SyntheticStream


@pytest.fixture(scope="module")
def slots():
    """Drifted slot arrays: stream a few batches so the slot array has real
    gaps/tombstones — the layout a checkpoint must preserve exactly."""
    g = rmat_graph(7, 6, seed=0)
    order = ordering.geo_order(g, seed=0)
    o = IncrementalOrderer(
        g.src[order].astype(np.int64), g.dst[order].astype(np.int64),
        g.num_vertices, regions=4,
    )
    stream = SyntheticStream(g, batch_size=48, delete_frac=0.3, seed=5)
    for _ in range(6):
        o.apply(stream.batch())
    o.needs_resync = False
    o.drain_ops()
    return g, o


def orderer_tree(g, o):
    """The checkpointable orderer state: the slot triple IS the stream's
    durable state (dicts/devices rebuild from it)."""
    return {
        "slot": {
            "src": o.slot_src.copy(),
            "dst": o.slot_dst.copy(),
            "valid": o.slot_valid.copy(),
        },
        "meta": np.asarray([g.num_vertices, o.regions], dtype=np.int64),
    }


# ------------------------------------------------------------ layout contract
def test_shard_files_hold_exact_cep_chunks(tmp_path, slots):
    """Disk contract: shard_<h>.npz holds, per tensor, exactly the CEP chunk
    [bounds[h], bounds[h+1]) of the FLATTENED tensor — so a replacement host
    can address its chunk without reading any other shard."""
    g, o = slots
    tree = orderer_tree(g, o)
    k = 5
    d = store.save(tree, tmp_path, step=2, k_shards=k)
    manifest = json.loads((d / "manifest.json").read_text())
    assert manifest["step"] == 2 and manifest["k_shards"] == k
    named = {t["name"]: t for t in manifest["tensors"]}
    assert set(named) == {"slot/src", "slot/dst", "slot/valid", "meta"}
    for h in range(k):
        with np.load(d / f"shard_{h}.npz") as z:
            for name, t in named.items():
                flat = np.asarray(tree["slot"][name.split("/")[1]] if "/" in name
                                  else tree[name]).reshape(-1)
                b = cep.chunk_bounds(flat.shape[0], k)
                np.testing.assert_array_equal(z[name], flat[int(b[h]):int(b[h + 1])])


def test_chunks_partition_each_tensor(tmp_path, slots):
    """Concatenating every shard's chunk of a tensor reproduces the flattened
    tensor with nothing dropped or duplicated."""
    g, o = slots
    tree = orderer_tree(g, o)
    k = 3
    d = store.save(tree, tmp_path, step=0, k_shards=k)
    chunks = []
    for h in range(k):
        with np.load(d / f"shard_{h}.npz") as z:
            chunks.append(z["slot/src"])
    np.testing.assert_array_equal(np.concatenate(chunks), o.slot_src)


# ----------------------------------------------- pack_slots layout round-trip
@pytest.mark.parametrize("k_new", [4, 6, 2])
def test_pack_slots_layout_roundtrip_resharded(tmp_path, slots, k_new):
    """The full streaming pack (edges/mask/degrees, scratch column included)
    plus the orderer slot state round-trips byte-exactly through save at k=4
    and restore at any k' — resharding must never touch a byte's VALUE, only
    where it lives."""
    g, o = slots
    pack = GE.pack_slots(o.slot_src, o.slot_dst, o.slot_valid, o.regions, g.num_vertices)
    tree = dict(orderer_tree(g, o), pack={
        "edges": pack.edges,
        "mask": pack.mask,
        "degrees": pack.degrees,
    })
    store.save(tree, tmp_path, step=7, k_shards=4)
    restored, bytes_touched = store.restore(tmp_path, 7, k_new=k_new, template=tree)
    for name in ("src", "dst", "valid"):
        np.testing.assert_array_equal(restored["slot"][name], tree["slot"][name])
        assert restored["slot"][name].dtype == tree["slot"][name].dtype
    for name in ("edges", "mask", "degrees"):
        np.testing.assert_array_equal(restored["pack"][name], tree["pack"][name])
    # Internal consistency: re-packing the restored slot state reproduces the
    # restored pack — slot state and pack stayed mutually coherent.
    repack = GE.pack_slots(
        restored["slot"]["src"], restored["slot"]["dst"], restored["slot"]["valid"],
        o.regions, g.num_vertices,
    )
    np.testing.assert_array_equal(repack.edges, restored["pack"]["edges"])
    np.testing.assert_array_equal(repack.mask, restored["pack"]["mask"])
    assert (bytes_touched == 0) == (k_new == 4)


def test_bytes_touched_matches_cep_model(tmp_path, slots):
    """bytes_touched is exactly Σ_tensors migrated_edges_exact(|T|, k, k')
    · itemsize — the Thm.-2 restore bill, not a full-reshuffle bill."""
    g, o = slots
    tree = orderer_tree(g, o)
    k_old, k_new = 4, 7
    store.save(tree, tmp_path, step=1, k_shards=k_old)
    _, bytes_touched = store.restore(tmp_path, 1, k_new=k_new)
    expect = 0
    for _, a in (
        ("slot/src", o.slot_src), ("slot/dst", o.slot_dst),
        ("slot/valid", o.slot_valid), ("meta", np.zeros(2, np.int64)),
    ):
        a = np.asarray(a)
        expect += cep.migrated_edges_exact(a.size, k_old, k_new) * a.itemsize
    assert bytes_touched == expect
    # The whole point: far less than re-reading everything.
    total_bytes = sum(np.asarray(a).nbytes for a in
                      (o.slot_src, o.slot_dst, o.slot_valid)) + 16
    assert bytes_touched < total_bytes


def test_restore_without_template_returns_named_dict(tmp_path, slots):
    g, o = slots
    store.save(orderer_tree(g, o), tmp_path, step=4, k_shards=3)
    arrays, bytes_touched = store.restore(tmp_path, 4, k_new=3)
    assert set(arrays) == {"slot/src", "slot/dst", "slot/valid", "meta"}
    np.testing.assert_array_equal(arrays["slot/valid"], o.slot_valid)
    assert bytes_touched == 0


def test_tiny_tensor_survives_more_shards_than_elements(tmp_path):
    """A tensor with fewer elements than shards (and a scalar) must still
    round-trip: trailing shards carry empty chunks, not garbage."""
    tree = {"tiny": np.arange(3, dtype=np.int32), "scalar": np.float32(2.5)}
    store.save(tree, tmp_path, step=0, k_shards=6)
    arrays, _ = store.restore(tmp_path, 0, k_new=2)
    np.testing.assert_array_equal(arrays["tiny"], tree["tiny"])
    assert arrays["scalar"].shape == () and float(arrays["scalar"]) == 2.5

# -------------------------------------------------------------- error paths
def test_restore_missing_step_is_typed(tmp_path, slots):
    g, o = slots
    store.save(orderer_tree(g, o), tmp_path, step=3, k_shards=2)
    with pytest.raises(store.MissingStepError, match="step 9"):
        store.restore(tmp_path, 9, k_new=2)
    assert issubclass(store.MissingStepError, store.CheckpointError)


def test_restore_mismatched_template_treedef(tmp_path, slots):
    """A template whose treedef names different leaves must fail loudly with
    BOTH sides of the diff — not silently reshape into the wrong pytree."""
    g, o = slots
    store.save(orderer_tree(g, o), tmp_path, step=0, k_shards=2)
    bad = {"slot": {"src": o.slot_src, "dst": o.slot_dst}, "extra": np.zeros(3)}
    with pytest.raises(store.TemplateMismatchError) as ei:
        store.restore(tmp_path, 0, k_new=2, template=bad)
    assert "extra" in str(ei.value) and "slot/valid" in str(ei.value)


def test_restore_missing_shard_file(tmp_path, slots):
    g, o = slots
    d = store.save(orderer_tree(g, o), tmp_path, step=1, k_shards=3)
    (d / "shard_1.npz").unlink()
    with pytest.raises(store.CorruptShardError, match="shard_1.npz missing"):
        store.restore(tmp_path, 1, k_new=3)


def test_restore_truncated_shard_file(tmp_path, slots):
    """A partially written shard (torn npz) is CorruptShardError, never a raw
    zipfile/np.load exception leaking through."""
    g, o = slots
    d = store.save(orderer_tree(g, o), tmp_path, step=1, k_shards=3)
    blob = (d / "shard_2.npz").read_bytes()
    (d / "shard_2.npz").write_bytes(blob[: len(blob) // 2])
    with pytest.raises(store.CorruptShardError, match="shard_2.npz"):
        store.restore(tmp_path, 1, k_new=3)


def test_restore_wrong_chunk_shape(tmp_path, slots):
    """A shard whose chunk length disagrees with the manifest bounds is
    corrupt even when the npz itself parses."""
    g, o = slots
    d = store.save(orderer_tree(g, o), tmp_path, step=2, k_shards=2)
    with np.load(d / "shard_0.npz") as z:
        tensors = {n: z[n] for n in z.files}
    tensors["slot/src"] = tensors["slot/src"][:-1]
    np.savez(d / "shard_0.npz", **tensors)
    with pytest.raises(store.CorruptShardError, match="manifest chunk"):
        store.restore(tmp_path, 2, k_new=2)


def test_slot_checkpoint_restore_without_manifest(tmp_path):
    ck = store.SlotCheckpoint(tmp_path)
    with pytest.raises(store.MissingStepError, match="no manifest"):
        ck.restore()


def _fresh_ck_pipeline(tmp_path, slots, interval=2):
    g, o_seed = slots
    o = IncrementalOrderer(
        o_seed.slot_src[o_seed.slot_valid].copy(),
        o_seed.slot_dst[o_seed.slot_valid].copy(),
        g.num_vertices, regions=4,
    )
    ck = store.SlotCheckpoint(tmp_path, interval=interval)
    stream = SyntheticStream(g, batch_size=32, delete_frac=0.3, seed=7)
    for step in range(4):
        b = stream.batch()
        o.apply(b)
        o.needs_resync = False
        o.drain_ops()
        ck.note_batch(o, b, step)
    return o, ck


def test_slot_checkpoint_missing_chunk_file(tmp_path, slots):
    o, ck = _fresh_ck_pipeline(tmp_path, slots)
    victim = next(tmp_path.glob("chunk_r2_s*.npz"))
    victim.unlink()
    with pytest.raises(store.CorruptShardError, match="chunk_r2"):
        store.SlotCheckpoint(tmp_path).restore()


def test_slot_checkpoint_truncated_chunk_file(tmp_path, slots):
    o, ck = _fresh_ck_pipeline(tmp_path, slots)
    victim = next(tmp_path.glob("chunk_r1_s*.npz"))
    blob = victim.read_bytes()
    victim.write_bytes(blob[: len(blob) // 2])
    with pytest.raises(store.CorruptShardError, match="unreadable"):
        store.SlotCheckpoint(tmp_path).restore()


def test_slot_checkpoint_manifest_missing_region(tmp_path, slots):
    o, ck = _fresh_ck_pipeline(tmp_path, slots)
    m = max(tmp_path.glob("manifest_*.json"),
            key=lambda p: int(p.stem.split("_")[1]))
    doc = json.loads(m.read_text())
    del doc["chunk_step"]["3"]
    m.write_text(json.dumps(doc))
    with pytest.raises(store.CorruptShardError, match="lacks region 3"):
        store.SlotCheckpoint(tmp_path).restore()
