"""``serve --backend jax``: the real detector pod through the entry point.

On CPU the pod runs at a small input size and ERP size (patched in the
test only; the entry point always serves the published sizes), so the
wiring of ``build_jax_pod`` stays covered at tier-1 cost.
"""

import dataclasses
import sys

import jax
import pytest

from repro.launch import serve
from repro.models import detector as det_mod


@pytest.fixture
def tiny_pod(monkeypatch, tmp_path):
    monkeypatch.setattr(serve, "JAX_POD_DETECTORS", tuple(
        dataclasses.replace(c, input_size=s)
        for c, s in zip(det_mod.PAPER_LADDER[:2], (64, 96))))
    monkeypatch.setattr(serve, "JAX_POD_ERP_HW", (64, 128))
    # an externally placed cache is left to JAX: nothing set in code
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def test_serve_backend_jax_open_loop(tiny_pod, monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", [
        "serve", "--backend", "jax", "--streams", "2", "--frames", "2",
        "--open-loop", "--jitter", "0", "--admission", "slo"])
    serve.main()
    out = capsys.readouterr().out
    assert "yolo-tiny-416@64px, yolo-csp-512@96px" in out
    served = next(line for line in out.splitlines()
                  if line.startswith("served "))
    assert int(served.split()[1]) > 0
    traces = next(line for line in out.splitlines()
                  if line.startswith("jit traces:"))
    assert int(traces.split()[3]) > 0  # the detector forward compiled


def test_build_jax_pod_shapes(tiny_pod):
    server, backend = serve.build_jax_pod(2, 2)
    assert [c.n_classes for c in backend.cfgs] == [80, 80]
    assert backend.fused and not backend.use_kernel
    frame = server.frame_source(1, 0)
    assert frame.shape == (64, 128, 3)
    assert server.frame_source(1, 0) is frame  # crop cache keys on it


def test_compile_cache_env_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert serve.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_repo_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = serve.configure_compile_cache()
        assert path == str(serve.REPO_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
