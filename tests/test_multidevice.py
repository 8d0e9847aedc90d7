"""Multi-(host-)device tests: run in subprocesses so XLA_FLAGS can force 8
devices without polluting the main test process (which must see 1 device)."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.launch.multihost import force_host_device_flags  # noqa: E402


def run_with_devices(code: str, n: int = 8, timeout: int = 420) -> str:
    """Run ``code`` in a child forced to ``n`` host devices.

    The device-count flag is built explicitly (force_host_device_flags strips
    any pre-existing count and preserves unrelated flags) — never patched with
    string substitution, which corrupts the value whenever the old count's
    digits appear elsewhere in the string. Only the child's env copy is
    touched; tests that must mutate ``os.environ`` in the child restore it in
    a ``finally`` (see test_production_mesh_shapes)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = force_host_device_flags(n, env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=ROOT,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_sp_decode_attention_matches_plain():
    run_with_devices("""
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch import mesh as MM
        from repro.models import dist as D
        from repro.models import layers as L

        mesh = MM.make_test_mesh(data=2, model=4)
        b, hq, hkv, hd, s = 4, 8, 2, 16, 64
        key = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (b, hq, 1, hd))
        ck = jax.random.normal(kk, (b, hkv, s, hd))
        cv = jax.random.normal(kv, (b, hkv, s, hd))
        pos = jnp.asarray(40, jnp.int32)

        dist = D.Distribution(mesh=mesh, batch_axes=("data",), seq_axes=("model",))
        with mesh:
            got = jax.jit(lambda q, k, v: D.sp_decode_attention(
                dist, q, k, v, pos, window=None, softcap=None, scale=hd**-0.5))(q, ck, cv)
        # Plain reference: mea_attention over the cache with kv_len mask.
        want = L.mea_attention(q, ck, cv, causal=True, q_offset=pos,
                               kv_len=jnp.full((b,), pos + 1, jnp.int32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)

        # Windowed + softcapped variant.
        with mesh:
            got_w = jax.jit(lambda q, k, v: D.sp_decode_attention(
                dist, q, k, v, pos, window=jnp.asarray(16), softcap=20.0, scale=hd**-0.5))(q, ck, cv)
        import repro.models.layers as L2
        import functools
        want_w = L.mea_attention(q, ck, cv, causal=True, q_offset=pos, window=jnp.asarray(16),
                                 softcap=20.0, kv_len=jnp.full((b,), pos + 1, jnp.int32))
        np.testing.assert_allclose(np.asarray(got_w), np.asarray(want_w), rtol=2e-5, atol=2e-5)
        print("SP-DECODE-OK")
    """)


def test_sp_cache_update_writes_owner_shard_only():
    run_with_devices("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.launch import mesh as MM
        from repro.models import dist as D

        mesh = MM.make_test_mesh(data=2, model=4)
        b, hkv, s, hd = 2, 2, 32, 8
        cache = jnp.zeros((b, hkv, s, hd))
        newk = jnp.ones((b, hkv, 1, hd))
        dist = D.Distribution(mesh=mesh, batch_axes=("data",), seq_axes=("model",))
        for pos in (0, 7, 8, 31):
            with mesh:
                out = jax.jit(lambda c, n: D.sp_cache_update(dist, c, n, jnp.asarray(pos)))(cache, newk)
            out = np.asarray(out)
            assert np.all(out[:, :, pos] == 1.0), pos
            mask = np.ones(s, bool); mask[pos] = False
            assert np.all(out[:, :, mask] == 0.0), pos
        print("CACHE-OK")
    """)


def test_full_decode_step_with_sp_matches_single_device():
    run_with_devices("""
        import jax, numpy as np, jax.numpy as jnp
        from repro import configs
        from repro.launch import mesh as MM
        from repro.models import dist as D, model as M

        cfg = configs.get_smoke("qwen3-8b")
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        b, s, maxlen = 2, 16, 32
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32),
                 "targets": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)}
        cache = M.init_cache(cfg, b, maxlen)
        logits_p, cache = jax.jit(lambda p, bt, c: M.forward_prefill(p, cfg, bt, c))(params, batch, cache)
        tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, 1)), jnp.int32)
        # Plain decode (no dist ctx).
        logits_plain, _ = jax.jit(lambda p, t, c: M.forward_decode(p, cfg, t, c))(params, tok, cache)
        # SP decode on a 2x4 mesh.
        mesh = MM.make_test_mesh(data=2, model=4)
        dist = D.Distribution(mesh=mesh, batch_axes=("data",), seq_axes=("model",))
        with mesh, D.use_distribution(dist):
            logits_sp, _ = jax.jit(lambda p, t, c: M.forward_decode(p, cfg, t, c))(params, tok, cache)
        np.testing.assert_allclose(np.asarray(logits_sp), np.asarray(logits_plain), rtol=5e-4, atol=5e-4)
        print("SP-FULL-OK")
    """)


def test_compressed_allreduce_dp_grads():
    run_with_devices("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.launch import mesh as MM
        from repro.train import compression as C

        mesh = MM.make_test_mesh(data=8, model=1)
        params = {"w": jnp.asarray(np.random.default_rng(0).standard_normal((16, 4)), jnp.float32)}
        batch = {"x": jnp.asarray(np.random.default_rng(1).standard_normal((32, 16)), jnp.float32),
                 "y": jnp.asarray(np.random.default_rng(2).standard_normal((32, 4)), jnp.float32)}

        def loss_fn(p, b):
            pred = b["x"] @ p["w"]
            return jnp.mean((pred - b["y"]) ** 2)

        err = jax.tree.map(lambda p: jnp.zeros_like(p), params)
        fn = C.make_compressed_dp_grad_fn(lambda p, b: loss_fn(p, b), mesh, axis="data")
        with mesh:
            loss, grads, err2 = jax.jit(fn)(params, batch, err)
        g_true = jax.grad(lambda p: loss_fn(p, batch))(params)
        rel = np.abs(np.asarray(grads["w"]) - np.asarray(g_true["w"])).max() / (np.abs(np.asarray(g_true["w"])).max() + 1e-9)
        assert rel < 0.05, rel  # int8 quantization error bound (shared pmax scale)
        assert float(loss) > 0
        print("COMPRESS-OK", rel)
    """)


def test_sharded_rescale_acceptance_8dev():
    """Tentpole acceptance inside tier-1: on 8 forced host devices, executing
    a ScalePlan on the graph-mesh-sharded buffers is bit-identical to the
    single-device pack_ordered oracle, and the reported cross-device migrated
    bytes equal ScalePlan.migrated_bytes (Thm. 2). Full coverage lives in
    tests/test_rescale_sharded.py (CI multidevice job)."""
    run_with_devices("""
        import numpy as np
        from repro.core import cep, ordering
        from repro.core.graph import rmat_graph
        from repro.elastic.rescale_exec import ElasticRescaler
        from repro.graphs import engine as E
        from repro.graphs.engine import EDGE_BYTES
        from repro.launch import mesh as MM

        g = rmat_graph(8, 6, seed=0)
        order = ordering.geo_order(g, seed=0)
        src, dst = g.src[order], g.dst[order]
        mesh = MM.make_graph_mesh(8)
        r = ElasticRescaler()

        d8 = E.pack_ordered_sharded(src, dst, g.num_vertices, 8, mesh)
        plan_out = cep.scale_plan(g.num_edges, 8, 12)
        d12, s_out = r.execute(d8, plan_out, verify=True)
        assert s_out.devices == 8
        assert s_out.cross_device_bytes == plan_out.migrated_bytes(EDGE_BYTES)
        # GAS runs directly over the sharded rows (k=12 ∤ 8 devices is fine);
        # must happen before the scale-in donates d12's buffers.
        pr = np.asarray(E.pagerank(d12, iterations=10))
        ref = E.pack_ordered_sharded(src, dst, g.num_vertices, 12, MM.make_graph_mesh(1))
        pr_ref = np.asarray(E.pagerank(ref, iterations=10))
        np.testing.assert_allclose(pr, pr_ref, rtol=1e-6, atol=1e-9)
        plan_in = cep.scale_plan(g.num_edges, 12, 8)
        back, s_in = r.execute(d12, plan_in, verify=True)
        assert s_in.cross_device_bytes == plan_in.migrated_bytes(EDGE_BYTES)
        orig = E.pack_ordered(src, dst, g.num_vertices, 8)
        got = E.unshard_engine_data(back)
        np.testing.assert_array_equal(got.edges, orig.edges)
        np.testing.assert_array_equal(got.mask, orig.mask)
        print("SHARDED-RESCALE-OK")
    """)


def test_sharded_stream_ingest_acceptance_8dev():
    """Streaming acceptance inside tier-1: on 8 forced host devices, on-device
    ingest + two rescales-under-ingest stay bit-identical to the host slot
    oracle, and ingest+scale events share one monotonic seq. Full coverage
    lives in tests/test_stream_sharded.py (CI multidevice job)."""
    run_with_devices("""
        import numpy as np
        from repro.core import ordering
        from repro.core.graph import rmat_graph
        from repro.elastic import controller as ec
        from repro.launch import mesh as MM
        from repro.stream import IncrementalOrderer, StreamingEngine, SyntheticStream

        g = rmat_graph(8, 6, seed=0)
        order = ordering.geo_order(g, seed=0)
        src, dst = g.src[order].astype(np.int64), g.dst[order].astype(np.int64)
        o = IncrementalOrderer(src, dst, g.num_vertices, regions=8)
        eng = StreamingEngine(o, MM.make_graph_mesh(8))
        t = [0.0]
        ctl = ec.ElasticController(8, dead_after_s=5.0, clock=lambda: t[0])
        ctl.attach_stream(eng)
        stream = SyntheticStream(g, batch_size=64, seed=1)

        ctl.ingest(stream.batch())
        ev_up = ctl.add_hosts(4)          # k → k+x under ingest
        assert ev_up.executed and eng.k == 12
        eng.verify_bit_identity()
        ctl.ingest(stream.batch())
        t[0] = 1.0
        for h in range(7):
            ctl.heartbeat(h, 1)
        t[0] = 6.0
        ev_down = ctl.poll()              # k → k−y (5 silent hosts preempted)
        assert ev_down is not None and ev_down.executed and eng.k == 7
        ctl.ingest(stream.batch())
        eng.verify_bit_identity()
        inc, oracle = eng.rf_vs_oracle()
        assert inc <= oracle * o.config.rf_margin + 1e-9, (inc, oracle)
        seqs = [e.seq for e in ctl.events]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
        print("SHARDED-STREAM-OK")
    """)


def test_production_mesh_shapes():
    """512 forced devices come from run_with_devices(n=512) building the flag
    explicitly. The child re-asserts the count instead of patching XLA_FLAGS
    with str.replace (which corrupted the flag whenever the digits of the old
    count appeared in the new one), and any env mutation it does make is
    restored in a finally."""
    run_with_devices("""
        import os
        from repro.launch.multihost import force_host_device_flags
        saved = os.environ.get("XLA_FLAGS")
        os.environ["XLA_FLAGS"] = force_host_device_flags(512, saved or "")
        try:
            import jax
            from repro.launch import mesh as MM
            m1 = MM.make_production_mesh()
            assert m1.devices.shape == (16, 16) and m1.axis_names == ("data", "model")
            m2 = MM.make_production_mesh(multi_pod=True)
            assert m2.devices.shape == (2, 16, 16) and m2.axis_names == ("pod", "data", "model")
            assert MM.num_chips(m2) == 512
        finally:
            if saved is None:
                os.environ.pop("XLA_FLAGS", None)
            else:
                os.environ["XLA_FLAGS"] = saved
        print("MESH-OK")
    """, n=512)
