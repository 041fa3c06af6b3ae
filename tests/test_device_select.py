"""What the program reads from the device instead of assuming it, on the
CPU: the chip spec chosen by ``device_kind`` and capped at the runtime's
HBM limit, the persistent compile cache's directory, the page's VMEM
footprint, the pool's lane-padded head dim, and the ``proc`` transport's
refusal to spawn replicas that would need a chip this process holds."""

from pathlib import Path

import jax
import pytest

from repro.core.plan import page_vmem_bytes
from repro.hw import tpu
from repro.launch import compile_cache


class _FakeTpu:
    platform = "tpu"

    def __init__(self, kind, limit):
        self.device_kind = kind
        self._limit = limit

    def memory_stats(self):
        return {"bytes_limit": self._limit} if self._limit else None


@pytest.fixture
def attached(monkeypatch):
    """Pretend one TPU of a given kind (and HBM limit) is attached."""

    def attach(kind, limit=None):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(jax, "devices", lambda *a: [_FakeTpu(kind, limit)])
        tpu._attached_chip.cache_clear()

    yield attach
    tpu._attached_chip.cache_clear()


class TestChipSpecFromDevice:
    def test_named_spec_is_the_table_entry(self):
        assert tpu.chip_spec("tpu_v5e") is tpu.TPU_V5E
        small = tpu.chip_spec("tpu_v5e", vmem_bytes=1 << 20)
        assert small.vmem_bytes == 1 << 20 and small.name == "tpu_v5e"

    def test_off_tpu_the_default_is_the_planning_target(self):
        tpu._attached_chip.cache_clear()
        assert tpu.chip_spec() is tpu.TPU_V5E

    @pytest.mark.parametrize("kind,name", [
        ("TPU v5 lite", "tpu_v5e"),
        ("TPU v4", "tpu_v4"),
        ("TPU v5p", "tpu_v5p"),
    ])
    def test_kind_selects_the_spec(self, attached, kind, name):
        attached(kind)
        assert tpu.chip_spec().name == name

    def test_hbm_is_capped_at_the_runtime_limit(self, attached):
        limit = 16_909_336_064                 # what a v5e reports
        attached("TPU v5 lite", limit)
        spec = tpu.chip_spec()
        assert spec.hbm_bytes == min(tpu.TPU_V5E.hbm_bytes, limit)
        assert spec.hbm_bytes <= limit

    def test_unknown_kind_is_an_error(self, attached):
        attached("TPU v99 mega")
        with pytest.raises(ValueError, match="TPU v99 mega"):
            tpu.chip_spec()


class TestCompileCache:
    def test_env_directory_is_left_alone(self, monkeypatch):
        calls = []
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        monkeypatch.setattr(jax.config, "update",
                            lambda *a: calls.append(a))
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert calls == []

    def test_default_is_fixed_in_the_checkout(self, monkeypatch):
        calls = []
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(jax.config, "update",
                            lambda *a: calls.append(a))
        root = Path(__file__).resolve().parents[1]
        assert compile_cache.CACHE_DIR == root / ".jax_cache"
        assert compile_cache.enable_compile_cache() == str(root / ".jax_cache")
        assert calls == [("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))]


class TestPageFootprint:
    def test_head_dim_pads_to_lanes(self):
        # K/V blocks hold the head dim on the 128 lanes: 64 costs what
        # 128 does.
        assert page_vmem_bytes(256, 8, 64, 32, 2) == \
            page_vmem_bytes(256, 8, 128, 32, 2)

    def test_grows_with_the_page(self):
        sizes = [page_vmem_bytes(t, 32, 64, 32, 4) for t in (8, 256, 688)]
        assert sizes == sorted(sizes) and len(set(sizes)) == 3

    def test_exceeds_the_raw_double_buffered_page(self):
        raw = 2 * 2 * 688 * 32 * 64 * 4          # K+V, two buffers, f32
        assert page_vmem_bytes(688, 32, 64, 32, 4) > 2 * raw


class TestPoolHeadDim:
    @pytest.mark.parametrize("d,want", [(16, 128), (64, 128), (128, 128),
                                        (192, 256)])
    def test_whole_lane_rows(self, d, want):
        from repro.serve.pages import pool_head_dim
        assert pool_head_dim(d) == want

    def test_pool_page_prices_the_padding(self):
        from repro.configs import get_model_config
        from repro.serve.kvcache import PageSpec
        from repro.serve.pages import pool_page_bytes

        cfg = get_model_config("stablelm-1.6b")       # head_dim 64
        page = PageSpec(page_tokens=688, token_bytes=1000)
        assert pool_page_bytes(cfg, page) == 2 * page.page_bytes


def test_proc_transport_refuses_a_tpu(monkeypatch):
    from repro.cluster.worker import Replica, StubSpec

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="thread"):
        Replica(StubSpec(), transport="proc")
