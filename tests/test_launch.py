"""Launcher tests: the serve entry point runs end to end on its normal
path, meshes lower under the installed JAX, and nothing hides which
device a run used (compile cache, profiler, roofline profile)."""

import jax
import pytest

from repro.launch import compile_cache, serve
from repro.launch.mesh import make_local_mesh
from repro.obs.profile import device_trace
from repro.parallel.sharding import use_mesh
from repro.roofline.analysis import hw_profile

SMOKE = ["--arch", "qwen3-0.6b_smoke", "--kv-layout", "paged",
         "--gemm-backend", "int8", "--requests", "2", "--prompt-len", "20",
         "--max-new", "3", "--prefill-chunk", "8"]


@pytest.fixture
def env_cache_dir(monkeypatch, tmp_path):
    """An explicit JAX_COMPILATION_CACHE_DIR: the launchers then configure
    nothing, so a test run leaves the process's cache settings alone."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jcc"))
    return tmp_path / "jcc"


def test_local_mesh_axes_are_auto():
    mesh = make_local_mesh(1, 1)
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,) * 2


def test_serve_main_paged_int8_completes(env_cache_dir):
    done = serve.main(SMOKE)
    assert len(done) == 2 and all(len(r.out) == 3 for r in done)


def test_logits_hook_sees_the_sampled_logits(env_cache_dir):
    args = serve.parse_args(SMOKE)
    seen = []
    with use_mesh(make_local_mesh(args.data, args.model)):
        cfg, _, eng, _ = serve.build_engine(args)
        eng.logits_hook = lambda rids, lg: seen.append((rids, lg))
        for req in serve.synthetic_requests(cfg, args):
            eng.submit(req)
        done = eng.run()
    assert all(lg.shape == (len(rids), cfg.vocab_size) for rids, lg in seen)
    for r in done:   # greedy: a request's last token is its last tick's argmax
        last = [lg[rids.index(r.rid)] for rids, lg in seen if r.rid in rids][-1]
        assert int(last.argmax()) == r.out[-1]


def test_compile_cache_env_wins(env_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(env_cache_dir)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.CHECKOUT_CACHE_DIR)
        assert compile_cache.CHECKOUT_CACHE_DIR.parent.joinpath("chip_smoke.py").is_file()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def _no_profiler(logdir):
    raise RuntimeError("profiler unavailable")


def test_device_trace_raises_when_profiler_cannot_start(monkeypatch, tmp_path):
    monkeypatch.setattr(jax.profiler, "start_trace", _no_profiler)
    with pytest.raises(RuntimeError, match="could not start"):
        with device_trace(str(tmp_path)):
            pass
    with device_trace(None):   # no logdir: no profiler call at all
        pass


def test_serve_fails_without_device_trace(monkeypatch, tmp_path, env_cache_dir):
    monkeypatch.setattr(jax.profiler, "start_trace", _no_profiler)
    with pytest.raises(RuntimeError, match="could not start"):
        serve.main([*SMOKE, "--profile-dir", str(tmp_path / "prof")])


def test_hw_profile_refuses_unknown_backend(monkeypatch):
    assert hw_profile("auto").name == jax.default_backend()
    monkeypatch.setattr(jax, "default_backend", lambda: "mystery")
    with pytest.raises(KeyError, match="mystery"):
        hw_profile(None)
