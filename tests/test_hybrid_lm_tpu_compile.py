"""The layer-typed LM's mixers compile for a v5e at the published widths and
the benchmark's sequence length (no chip: the TPU compiler is installed and
compiles for a described device; docs/HYBRID_LM.md). What it guards: a slice
the tiling refuses, a loop the compiler cannot lower, a working set that does
not fit. One file, and the topology only inside a fixture: one xdist worker
loads the TPU's library."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from multiverso_tpu.models.hybrid_lm import (HybridLMConfig, layer_forward,
                                             param_shapes)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 8192


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cfg():
    return HybridLMConfig.from_file(os.path.join(
        ROOT, "benchmark", "configs", "nemotron3-nano-30b-a3b-ep16.json"))


# (letter, index of such a layer in the pattern, sequences, HBM the layer's
# forward + backward may take beside its arguments, GB)
@pytest.mark.parametrize("kind,layer,seqs,temp_gb", [
    ("M", 0, 1, 4.0), ("*", 5, 1, 2.5), ("E", 1, 2, 2.5)])
def test_mixer_compiles_for_v5e_at_published_widths(one_chip, cfg, kind,
                                                    layer, seqs, temp_gb):
    assert cfg.pattern[layer] == kind

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p = {k: spec(s) for k, s in param_shapes(cfg)["layers"][layer].items()}
    bias = spec((cfg.router_experts,)) if kind == "E" else None
    u = spec((seqs, SEQ, cfg.hidden_size))

    def loss(p, bias, u):
        return jnp.sum(layer_forward(kind, p, bias, u, cfg, remat=True)[0])

    run = jax.grad(loss, argnums=(0, 2))
    compiled = jax.jit(run).lower(p, bias, u).compile()
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes < temp_gb * 1e9, stats
