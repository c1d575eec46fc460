"""Tests for the logical-axis sharding rules (divisibility + uniqueness)."""
import subprocess
import sys
import os
import textwrap



_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    import sys
    sys.path.insert(0, "src")
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.sharding import logical_to_spec, AbstractParam, tree_shardings
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh()                      # (16,16) data,model

    # divisible dims shard
    s = logical_to_spec(("vocab", "embed"), (163840, 7168), mesh)
    assert s == P("model", "data"), s
    # non-divisible dims replicate (GSPMD rejects uneven explicit shardings)
    s = logical_to_spec(("heads", None), (40, 128), mesh)
    assert s == P(None, None), s
    # kv_heads=8 < 16 replicates
    s = logical_to_spec((None, None, "kv_heads", None), (1, 5, 8, 128), mesh)
    assert s[2] is None, s
    # a mesh axis is used at most once per spec: batch=1 frees `data`
    # for the cache_seq dim
    s = logical_to_spec(("batch", "cache_seq", "kv_heads", None),
                        (1, 524288, 8, 128), mesh)
    assert s == P(None, "data", None, None), s
    # batch=128 takes data; cache_seq then replicates
    s = logical_to_spec(("batch", "cache_seq", "kv_heads", None),
                        (128, 32768, 8, 128), mesh)
    assert s == P("data", None, None, None), s

    # multi-pod: batch takes (pod, data)
    mesh2 = make_production_mesh(multi_pod=True)
    s = logical_to_spec(("batch", None), (256, 7), mesh2)
    assert s == P(("pod", "data"), None), s

    # tree_shardings works on AbstractParam trees
    tree = {"w": AbstractParam((512, 256), "float32", ("embed", "ffn"))}
    sh = tree_shardings(tree, mesh)
    assert sh["w"].spec == P("data", "model"), sh
    print("SHARDING_OK")
""")


def test_sharding_rules_on_production_mesh():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", _SCRIPT],
                         capture_output=True, text=True, timeout=300,
                         cwd=os.path.join(os.path.dirname(__file__), ".."),
                         env=env)
    assert "SHARDING_OK" in res.stdout, res.stderr[-2000:]


def test_param_count():
    from repro.sharding import AbstractParam, param_count
    tree = {"a": AbstractParam((3, 4), "float32", (None, None)),
            "b": AbstractParam((5,), "float32", (None,))}
    assert param_count(tree) == 17


_FLASH_DECODE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.attention import (AttnConfig, init_gqa, init_gqa_cache,
                                        gqa_decode)
    from repro.models.common import ParamFactory
    from repro.sharding import ParallelContext, rules_dict

    cfg = AttnConfig(d_model=32, n_heads=4, n_kv_heads=2, head_dim=16)
    pf = ParamFactory(jax.random.PRNGKey(0), jnp.float32)
    params = init_gqa(pf, cfg)
    B, S = 4, 64
    cache = {k: jax.random.normal(jax.random.PRNGKey(7), v.shape)
             for k, v in init_gqa_cache(cfg, B, S, jnp.float32).items()}
    x = jax.random.normal(jax.random.PRNGKey(1), (B, 1, 32))
    pos = jnp.int32(40)
    y_ref, c_ref = gqa_decode(params, cfg, x, pos, cache, ParallelContext())

    from repro.sharding import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = rules_dict({"cache_seq": ("data", "model")})
    ctx = ParallelContext(mesh=mesh, rules=rules)
    y_sh, c_sh = jax.jit(lambda p, x, c: gqa_decode(p, cfg, x, pos, c, ctx))(
        params, x, cache)
    np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-4)
    # entries the step did not write stay bit-identical; the written row
    # is a fresh fp32 projection (32-term dot) + RoPE, which the
    # partitioner may fuse/reorder: error <= ~(32+2) * eps_fp32 (1.2e-7)
    # relative to the summed magnitudes, i.e. ~4e-6 -> rtol 1e-5
    ck_sh, ck_ref = np.asarray(c_sh["k"]), np.asarray(c_ref["k"])
    np.testing.assert_array_equal(np.delete(ck_sh, 40, axis=1),
                                  np.delete(ck_ref, 40, axis=1))
    np.testing.assert_allclose(ck_sh[:, 40], ck_ref[:, 40],
                               atol=1e-6, rtol=1e-5)
    print("FLASH_DECODE_OK")
""")


def test_flash_decode_seq_sharded_cache_matches_dense():
    """Distributed flash-decode (partial max/lse/pv + psum over the
    seq-sharded KV cache) == single-device decode attention."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", _FLASH_DECODE_SCRIPT],
                         capture_output=True, text=True, timeout=300,
                         cwd=os.path.join(os.path.dirname(__file__), ".."),
                         env=env)
    assert "FLASH_DECODE_OK" in res.stdout, res.stderr[-2000:]
