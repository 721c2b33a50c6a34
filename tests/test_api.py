"""The plan/compile/execute API: config layer, plan caching, solver
execution, and the deprecated cp_decompose shim's equivalence."""
import numpy as np
import pytest

import repro.api as api
from repro.core import partition as partition_mod
from repro.core.decompose import CPResult, cp_decompose


# -- config layer -------------------------------------------------------------

def test_presets():
    paper = api.preset("paper")
    assert paper.partition.replication == 1      # no intra-group merge
    assert not paper.kernel.use_kernel
    assert paper.exchange.ring
    opt = api.preset("optimized")
    assert opt.partition.replication is None     # auto per-mode pick
    assert opt.kernel.resolved_variant() == "blocked"
    fused = api.preset("fused")
    assert fused.kernel.resolved_variant() == "fused"
    assert fused.kernel.autotune
    with pytest.raises(ValueError, match="unknown preset"):
        api.preset("nope")


def test_dotted_overrides():
    cfg = api.DecomposeConfig()
    out = cfg.with_overrides({"rank": 64, "kernel.variant": "fused",
                              "runtime.tol": 0.0})
    assert out.rank == 64
    assert out.kernel.variant == "fused"
    assert out.runtime.tol == 0.0
    assert cfg.rank == 32  # frozen: original untouched
    with pytest.raises(ValueError, match="no field"):
        cfg.with_overrides({"kernel.bogus": 1})
    with pytest.raises(ValueError, match="unknown config section"):
        cfg.with_overrides({"bogus.field": 1})
    with pytest.raises(ValueError, match="too deep"):
        cfg.with_overrides({"a.b.c": 1})
    # a whole section can be swapped for a config object, not a scalar typo
    out = cfg.with_overrides({"kernel": api.KernelConfig(use_kernel=True)})
    assert out.kernel.use_kernel
    with pytest.raises(ValueError, match="dotted path"):
        cfg.with_overrides({"kernel": "fused"})


def test_apply_set_args():
    cfg = api.apply_set_args(api.DecomposeConfig(), [
        "rank=16", "runtime.tol=1e-4", "exchange.ring=false",
        "partition.replication=none", "kernel.variant=fused"])
    assert cfg.rank == 16
    assert cfg.runtime.tol == pytest.approx(1e-4)
    assert cfg.exchange.ring is False
    # Python-style capitalization must not become a truthy string
    cfg = api.apply_set_args(cfg, ["exchange.ring=False"])
    assert cfg.exchange.ring is False
    cfg = api.apply_set_args(cfg, ["exchange.ring=True", "rank=None"])
    assert cfg.exchange.ring is True and cfg.rank is None
    assert cfg.partition.replication is None
    assert cfg.kernel.variant == "fused"
    with pytest.raises(ValueError, match="key=value"):
        api.apply_set_args(cfg, ["rank"])


def test_config_json_roundtrip():
    cfg = api.preset("fused", {"rank": 8, "runtime.checkpoint_dir": "/tmp/x"})
    back = api.DecomposeConfig.from_json(cfg.to_json())
    assert back == cfg


def test_kernel_kwargs_resolution():
    from repro.kernels import ops as kops
    kw = api.KernelConfig(use_kernel=False).mttkrp_kwargs()
    assert kw == {"use_kernel": False, "variant": "ref", "num_buffers": 2}
    kw = api.KernelConfig(use_kernel=True, variant="fused",
                          num_buffers=3).mttkrp_kwargs()
    assert kw == {"use_kernel": True, "variant": "fused", "num_buffers": 3}
    # the helper and the config agree (same resolution point)
    assert kw == kops.kernel_kwargs_from_config(
        api.KernelConfig(use_kernel=True, variant="fused", num_buffers=3))


def test_kernel_kwargs_autotuned_num_buffers(monkeypatch):
    """autotune=True picks up the tuned ring depth when the problem key is
    given; an explicit num_buffers always wins."""
    from repro.kernels import autotune
    monkeypatch.setattr(
        autotune, "autotune_ec",
        lambda nmodes, rank, variant: autotune.ECConfig(8, 128, 5))
    cfg = api.KernelConfig(use_kernel=True, variant="fused", autotune=True)
    assert cfg.mttkrp_kwargs(nmodes=3, rank=8)["num_buffers"] == 5
    assert cfg.mttkrp_kwargs()["num_buffers"] == 2       # no problem key
    explicit = api.KernelConfig(use_kernel=True, variant="fused",
                                autotune=True, num_buffers=4)
    assert explicit.mttkrp_kwargs(nmodes=3, rank=8)["num_buffers"] == 4


def test_legacy_kwargs_bridge():
    cfg = api.DecomposeConfig.from_legacy_kwargs(
        rank=8, num_devices=2, strategy="equal_nnz", use_kernel=True,
        kernel_variant="blocked", ring=False, tol=0.0, seed=9,
        checkpoint_dir="/tmp/c")
    assert cfg.rank == 8
    assert cfg.partition.strategy == "equal_nnz"
    assert cfg.kernel.resolved_variant() == "blocked"
    assert not cfg.exchange.ring
    assert cfg.runtime == api.RuntimeConfig(
        num_devices=2, checkpoint_dir="/tmp/c", tol=0.0, seed=9)


def test_paper_config_presets():
    from repro.configs.amped_paper import PAPER_DEVICES, RANK, paper_config
    cfg = paper_config("paper")
    assert cfg.rank == RANK and cfg.runtime.num_devices == PAPER_DEVICES
    cfg = paper_config("fused", {"runtime.num_devices": 1})
    assert cfg.kernel.autotune and cfg.runtime.num_devices == 1


def test_legacy_setup_shims():
    """The deprecated *_setup helpers still accept PaperRun field names."""
    from repro.configs.amped_paper import optimized_setup, paper_setup
    with pytest.warns(DeprecationWarning, match="paper_setup"):
        cfg = paper_setup("amazon", num_devices=2, use_kernel=True,
                          kernel_variant="blocked", rank=8)
    assert cfg.runtime.num_devices == 2
    assert cfg.kernel.resolved_variant() == "blocked"
    assert cfg.rank == 8
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError, match="no field"):
            optimized_setup("amazon", bogus_field=1)


# -- plan layer ---------------------------------------------------------------

def _cfg(**over):
    base = {"rank": 8, "runtime.tol": 0.0, "runtime.num_devices": 1}
    return api.preset("paper", {**base, **over})


def test_plan_cache_partitions_once(small_tensor, tmp_path, monkeypatch):
    calls = {"n": 0}
    real = partition_mod.build_plan

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(partition_mod, "build_plan", counting)
    api.reset_cache_stats()
    cfg = _cfg()
    p1 = api.plan(small_tensor, cfg, cache_dir=str(tmp_path))
    p2 = api.plan(small_tensor, cfg, cache_dir=str(tmp_path))
    assert calls["n"] == 1                      # second call never partitioned
    assert api.CACHE_STATS == {"hits": 1, "misses": 1}
    for d in range(p1.nmodes):
        for k in partition_mod.ModePartition.ARRAY_FIELDS:
            np.testing.assert_array_equal(getattr(p1.modes[d], k),
                                          getattr(p2.modes[d], k))


def test_plan_signature_sensitivity(small_tensor, small_tensor_4mode):
    cfg = _cfg()
    s0 = api.plan_signature(small_tensor, cfg, num_devices=1)
    assert s0 == api.plan_signature(small_tensor, cfg, num_devices=1)
    assert s0 != api.plan_signature(small_tensor_4mode, cfg, num_devices=1)
    assert s0 != api.plan_signature(small_tensor, cfg, num_devices=2)
    assert s0 != api.plan_signature(
        small_tensor, cfg.with_overrides({"partition.strategy": "equal_nnz"}),
        num_devices=1)
    assert s0 != api.plan_signature(
        small_tensor, cfg.with_overrides({"partition.tile": 16}),
        num_devices=1)


# -- execute layer ------------------------------------------------------------

def test_solver_sweep_and_result(small_tensor):
    cfg = _cfg()
    solver = api.compile(api.plan(small_tensor, cfg), cfg)
    s1 = solver.sweep()
    assert s1.sweep == 1
    s2 = solver.sweep()
    assert s2.sweep == 2 and len(s2.fits) == 2
    res = solver.result()
    assert isinstance(res, CPResult)
    assert res.sweeps == 2
    assert [f.shape for f in res.factors] == \
        [(s, cfg.rank) for s in small_tensor.shape]


def test_solver_reset(small_tensor):
    cfg = _cfg()
    solver = api.compile(api.plan(small_tensor, cfg), cfg)
    r1 = solver.run(2)
    solver.reset()
    r2 = solver.run(2)
    assert r1.fits == r2.fits  # same seed, same trajectory


def test_shim_matches_staged_api(small_tensor):
    cfg = _cfg(**{"runtime.seed": 3})
    staged = api.compile(api.plan(small_tensor, cfg), cfg).run(3)
    with pytest.warns(DeprecationWarning, match="cp_decompose"):
        legacy = cp_decompose(small_tensor, rank=8, num_devices=1, iters=3,
                              tol=0, seed=3)
    assert staged.fits == legacy.fits  # identical, not merely close
    for f1, f2 in zip(staged.factors, legacy.factors):
        np.testing.assert_array_equal(f1, f2)


def test_solver_checkpoint_restore_roundtrip(small_tensor, tmp_path):
    cfg = _cfg(**{"runtime.checkpoint_dir": str(tmp_path)})
    solver = api.compile(api.plan(small_tensor, cfg), cfg)
    full = solver.run(4)
    solver2 = api.compile(api.plan(small_tensor, cfg), cfg)
    assert solver2.restore()                       # latest = sweep 4
    assert solver2.state.sweep == 4
    resumed = solver2.run(4)                       # nothing left to do
    np.testing.assert_allclose(resumed.fits, full.fits, atol=1e-6)
    for f1, f2 in zip(resumed.factors, full.factors):
        np.testing.assert_allclose(f1, f2, atol=1e-5)


def test_solver_restore_without_ckpt_dir_raises(small_tensor):
    cfg = _cfg()
    solver = api.compile(api.plan(small_tensor, cfg), cfg)
    with pytest.raises(ValueError, match="checkpoint_dir"):
        solver.restore()


# -- CPResult.reconstruct_at --------------------------------------------------

def test_reconstruct_at_matches_dense():
    rng = np.random.default_rng(0)
    a, b, c = (rng.uniform(0.2, 1, (n, 3)).astype(np.float32)
               for n in (6, 5, 4))
    lam = np.asarray([2.0, 0.5, 1.0], np.float64)
    dense = np.einsum("r,ir,jr,kr->ijk", lam, a, b, c)
    res = CPResult(factors=[a, b, c], lam=lam, fits=[], plan=None, sweeps=0)
    ii, jj, kk = np.meshgrid(range(6), range(5), range(4), indexing="ij")
    coords = np.stack([ii.ravel(), jj.ravel(), kk.ravel()], axis=1)
    got = res.reconstruct_at(coords).reshape(6, 5, 4)
    np.testing.assert_allclose(got, dense, rtol=1e-5)


# -- compile cache (entry points) ---------------------------------------------

_CACHE_SCRIPT = r"""
import json, sys
import jax, jax.numpy as jnp
from repro.launch import compile_cache
path = compile_cache.enable()
if sys.argv[1:] == ["compile"]:
    jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
print(json.dumps({"path": path,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(from_env, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` wins and receives the entries; unset,
    the cache is the fixed ``<repo>/.jax_cache``."""
    import json
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    # unset, nothing is compiled: the test writes nothing in the checkout
    argv = ["compile"] if from_env else []
    out = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT, *argv],
                         env=env, text=True, capture_output=True,
                         timeout=300, check=True)
    got = json.loads(out.stdout.strip().splitlines()[-1])
    if from_env:
        assert got["path"] == got["config"] == str(tmp_path)
        assert os.listdir(tmp_path)
    else:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got["path"] == got["config"] == os.path.join(repo,
                                                            ".jax_cache")
