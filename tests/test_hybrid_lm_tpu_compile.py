"""The layer-typed LM's mixers, the grouped row programs of the device
form and word2vec's fused block programs (one chip; a 2 x 2 mesh) compile
for a v5e at the published widths and the benchmark's sequence length (no chip: the TPU compiler is installed and
compiles for a described device; docs/HYBRID_LM.md). What it guards: a slice
the tiling refuses, a loop the compiler cannot lower, a working set that does
not fit. One file, and the topology only inside a fixture: one xdist worker
loads the TPU's library."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from multiverso_tpu.core.options import AddOption
from multiverso_tpu.core.table import build_row_update, fused_rows_selected
from multiverso_tpu.core.updater import get_updater
from multiverso_tpu.models.hybrid_lm import (HybridLMConfig, init_buffers,
                                             layer_forward, param_shapes)
from multiverso_tpu.tables.table_group import (build_group_access,
                                               build_group_update,
                                               group_scalars)
from multiverso_tpu.telemetry.device_scopes import (parse_scopes,
                                                    scope_names)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 8192


def _scopes_of(compiled) -> set:
    """Every path component of the executable's instructions' op_names, the
    wrappers jax puts around a scope (``transpose(jvp(...))``) taken off."""
    return {name for path in parse_scopes(compiled.as_text())[1].values()
            for name in scope_names(path)}


def _experts_on_the_row_kernel(compiled) -> None:
    """An expert block compiled with ``moe_rows_interpret=False``: the
    backward's block loop (and the forward's, where the program keeps one)
    ends in the DMA row kernel under ``lm_experts``, and no XLA scatter
    touches a [tokens, hidden] accumulator (ISSUE 41; the grouping's own
    scatters lay out [slots] ids and gates, the weight gradients' are
    [experts, ...])."""
    text = compiled.as_text()
    kernels = [path for name, path in parse_scopes(text)[1].items()
               if "pallas_call" in path]
    assert kernels and "tpu_custom_call" in text
    assert all("lm_experts" in scope_names(path) for path in kernels)
    scatters = [line for line in text.splitlines() if " scatter(" in line]
    assert not [line for line in scatters
                if line.split(" = ")[1].startswith("f32[")
                and line.split(" = ")[1].count(",") == 1], scatters


def _kernel_calls(text: str) -> list:
    """The op_name path of every Mosaic kernel's call in a compiled text."""
    import re
    return [re.search(r'op_name="([^"]*)"', line).group(1)
            for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def _experts_on_the_row_kernel_among_others(
        compiled, others=frozenset({"lm_mla"})) -> None:
    """Every Mosaic kernel of the program lies under the expert blocks' scope
    or under one of ``others`` (the latent-attention block's), and each of
    those scopes holds one."""
    kernels = _kernel_calls(compiled.as_text())
    scopes = {"lm_experts"} | set(others)
    assert kernels
    assert {name for path in kernels for name in scope_names(path)
            if name in scopes} == scopes
    assert all(scopes & set(scope_names(path)) for path in kernels)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
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

    # an expert block as HybridLM runs it on one chip: on the row kernel
    plane = (False,) if kind == "E" else ()

    def loss(p, bias, u):
        return jnp.sum(layer_forward(kind, p, bias, u, cfg, True, *plane)[0])

    run = jax.grad(loss, argnums=(0, 2))
    compiled = jax.jit(run).lower(p, bias, u).compile()
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes < temp_gb * 1e9, stats
    if kind == "E":
        _experts_on_the_row_kernel(compiled)


DSV2, EVABYTE = "deepseek-v2-lite-ep4", "evabyte-6.5b-pp8"
SALA = "minicpm-sala-9b-pp8"
LFM2 = "lfm2-8b-a1b-ep4"
OURO = "ouro-2.6b-pp8"
LING3 = "ling-3.0-flash-ep32"


# Two-block layers, forward + backward: (configuration, letter, its index in
# the pattern, sequences, positions a sequence, HBM beside the arguments, GB).
# Latent attention (192-wide rotary-carrying keys against 128-wide values),
# the dense gated feed-forward and the gated expert block with its balance
# loss at 2 x 8,192; EVA (windows of four attention blocks, 128 summaries a
# window, seven remote blocks for the last window's queries) and the
# feed-forward in slabs at ONE sequence of 16,384; at the same length the
# sparse block (the selection a query block at a time, the blocked walk under
# a mask that is data), the Lightning block (the chunked scan at a group a
# head and 128 x 128 states), each with the 16 heads the cell holds (1.58 and
# 2.09 GB read here; 2.99 and 4.04-4.17 with all 32), and a feed-forward of
# 16,384 (3.29). At 4 x 8,192 the gated short convolution (0.67), the ``*``
# block with its norm a head and rotary turn (0.64) and the expert block
# without shared leaves, 8 of 32 experts held, 4 a token (0.86). At 2 x 8,192
# the KDA block, its 32 heads of 128 a group of 8 at a time (2.43; all at once
# 4.59 before), latent attention of 32 heads without YaRN (1.46) and the expert block
# of 8 of 512 experts, 8 a token from 4 of 8 groups (0.69 with 16 held).
@pytest.mark.parametrize("config,kind,layer,seqs,length,temp_gb", [
    (DSV2, "L", 0, 1, SEQ, 2.5), (DSV2, "D", 1, 1, SEQ, 3.0),
    (DSV2, "E", 3, 2, SEQ, 3.0), (EVABYTE, "V", 0, 1, 2 * SEQ, 2.5),
    (EVABYTE, "D", 1, 1, 2 * SEQ, 2.5), (SALA, "S", 0, 1, 2 * SEQ, 2.0),
    (SALA, "N", 2, 1, 2 * SEQ, 2.5), (SALA, "D", 1, 1, 2 * SEQ, 3.7),
    (LFM2, "C", 0, 4, SEQ, 1.0), (LFM2, "*", 4, 4, SEQ, 1.0),
    (LFM2, "E", 5, 4, SEQ, 1.3), (LING3, "K", 0, 2, SEQ, 3.0),
    (LING3, "L", 10, 2, SEQ, 2.0), (LING3, "E", 5, 2, SEQ, 1.0)])
def test_two_block_layer_compiles_for_v5e_at_published_widths(
        one_chip, config, kind, layer, seqs, length, temp_gb):
    cfg = HybridLMConfig.from_file(os.path.join(
        ROOT, "benchmark", "configs", config + ".json"))
    assert cfg.pattern[layer] == kind

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p = {k: spec(s) for k, s in param_shapes(cfg)["layers"][layer].items()}
    u = spec((seqs, length, cfg.hidden_size))
    bias = init_buffers(cfg)[layer]     # a Lightning block's decay a head
    bias = None if bias is None else spec(bias.shape)

    plane = (False,) if kind == "E" else ()

    def loss(p, bias, u):
        out, _, *more = layer_forward(kind, p, bias, u, cfg, True, *plane)
        # past a balance loss: the counts a selection bias's update reads
        return jnp.sum(out) + sum(more[:int(cfg.balanced)])

    compiled = jax.jit(jax.grad(loss, argnums=(0, 2))).lower(
        p, bias, u).compile()
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes < temp_gb * 1e9, stats
    if kind == "E":
        _experts_on_the_row_kernel(compiled)


# The scan of a Mamba-2 block (64 heads of 64 in 8 groups, one sequence of
# 8,192) and of a Lightning block (16 heads of 128, a group a head, one of
# 16,384), forward and backward, as HybridLM runs it on one chip
# (``mixer_interpret=False``: the kernels of ``ops/pallas_ssd.py``) and as it
# runs anywhere else (the ``jax.numpy`` body, where the check below has to
# find what it is there to miss).
@pytest.mark.parametrize("plane", ["fused", "xla"])
@pytest.mark.parametrize("config,kind,layer,length", [
    ("nemotron3-nano-30b-a3b-ep16", "M", 0, SEQ), (SALA, "N", 2, 2 * SEQ)])
def test_scan_block_compiles_for_v5e_with_its_decay_planes_in_vmem(
        one_chip, config, kind, layer, length, plane):
    """On the kernels every ``pallas_call`` lies under ``lm_ssd``, and the
    compiled text holds no float32 tensor of ``chunk x chunk`` planes, a
    head's or more, nor an ``exp`` that makes one: the decay planes are gone,
    not moved. (The one tensor of that shape a Lightning block keeps is the
    backward's chunk-start states, ``state x head_dim`` a chunk and head.)"""
    import re
    cfg = HybridLMConfig.from_file(os.path.join(
        ROOT, "benchmark", "configs", config + ".json"))
    assert cfg.pattern[layer] == kind

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p = {k: spec(s) for k, s in param_shapes(cfg)["layers"][layer].items()}
    u = spec((1, length, cfg.hidden_size))
    bias = init_buffers(cfg)[layer]
    bias = None if bias is None else spec(bias.shape)
    scan = {"mixer_interpret": False} if plane == "fused" else {}

    def loss(p, bias, u):
        return jnp.sum(layer_forward(kind, p, bias, u, cfg, True, **scan)[0])

    compiled = jax.jit(jax.grad(loss, argnums=(0, 2))).lower(
        p, bias, u).compile()
    text = compiled.as_text()
    chunk, state, groups, wide = (
        (cfg.chunk_size, cfg.ssm_state_size, cfg.n_groups,
         cfg.d_inner // cfg.n_groups) if kind == "M" else
        (cfg.lightning_chunk, cfg.lightning_head_dim, cfg.lightning_nh,
         cfg.lightning_head_dim))
    assert chunk == 128
    states = f"f32[1,{length // chunk},{groups},{state},{wide}]"
    planes = set(re.findall(r"f32\[[\d,]*,128,128\]", text)) - {states}
    made = [line for line in text.splitlines() if " exponential(" in line
            and re.search(r"= f32\[[\d,]*,128,128\]", line)]
    kernels = [path for path in parse_scopes(text)[1].values()
               if "pallas_call" in path]
    if plane == "xla":
        assert planes and made and not kernels
        return
    assert not planes and not made, (planes, made)
    # the remat's forward and the backward's two walks (nothing reads the
    # first forward's output under a loss that is a sum); a Mamba-2 block's
    # other kernels are the passes round the scan (the test below)
    calls = _kernel_calls(text)
    assert sum("lm_ssd" in scope_names(path) for path in calls) == 3
    assert len(calls) == (11 if kind == "M" else 3)
    assert all("lm_ssd" in scope_names(path) or "lm_mamba2" in scope_names(
        path) for path in kernels)
    assert compiled.memory_analysis().temp_size_in_bytes < 2.2e9


def _moved_whole(text: str, least: float) -> list:
    """The compiled program's instructions that only MOVE ``least`` bytes of
    float32 or more: a ``slice``, ``pad`` or ``concatenate`` that stands
    alone, or a fusion of nothing but those and the sum of what they lay
    side by side (fusions' own bodies are not looked into: what is fused
    into a product or a pass over the values is not a copy)."""
    import re
    moves = {"slice", "pad", "concatenate"}
    plain = moves | {"parameter", "constant", "broadcast", "bitcast", "add",
                     "tuple"}
    bodies = {}
    for body in re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()", text):
        bodies[body.split(" (")[0].replace("ENTRY ", "")] = [
            m.groups() for m in re.finditer(
                r"^\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\("
                r"(.*)$", body, re.M)]
    found = []
    for name, body in bodies.items():
        if "fused_computation" in name:
            continue
        for instruction, shape, op, rest in body:
            size = sum(4 * int(np.prod([int(n) for n in dims.split(",")]))
                       for dims in re.findall(r"f32\[([\d,]+)\]", shape))
            if op == "fusion":
                inside = {o for _, _, o, _ in bodies[re.search(
                    r"calls=(%[\w.\-]+)", rest).group(1)]}
                op = "moves" if inside & moves and inside <= plain else op
            if size >= least and op in moves | {"moves"}:
                found.append((instruction, shape))
    return found


@pytest.mark.parametrize("plane", ["fused", "xla"])
def test_mamba_block_compiles_for_v5e_with_its_passes_fused(one_chip, cfg,
                                                            plane):
    """The Mamba-2 block at the published widths and S = 8,192, forward and
    backward with remat, as HybridLM runs it on one chip: the convolution's
    and the gated norm's kernels (``ops/pallas_mamba.py``) lie under
    ``lm_mamba2`` and outside ``lm_ssd``, and of the slices, pads and
    concatenates of 128 MB or more that XLA's passes leave under that scope
    at most ONE is left, the join of ``in_proj``'s cotangent (none where the
    compiler fuses the join into the two products that read it). On XLA's
    passes the check finds what it is there to miss."""
    from multiverso_tpu.models.hybrid_lm.model import passes_kernel_blocks
    assert passes_kernel_blocks(cfg) == cfg.pattern.count("M") == 4

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p = {k: spec(s) for k, s in param_shapes(cfg)["layers"][0].items()}
    u = spec((1, SEQ, cfg.hidden_size))
    passes = {"mixer_interpret": False} if plane == "fused" else {}

    def loss(p, u):
        return jnp.sum(layer_forward("M", p, None, u, cfg, True,
                                     **passes)[0])

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(p, u).compile()
    text = compiled.as_text()
    moved = _moved_whole(text, 128e6)
    stats = compiled.memory_analysis()
    print(f"Mamba-2 block, {plane}: temporaries "
          f"{stats.temp_size_in_bytes / 1e9:.3f} GB, moved whole: {moved}")
    kernels = _kernel_calls(text)
    if plane == "xla":
        assert not kernels and len(moved) > 1, moved
        return
    ours = [path for path in kernels if "lm_ssd" not in scope_names(path)]
    assert all("lm_mamba2" in scope_names(path) for path in ours)
    # three parts of the convolution (remat's forward, backward), the norm's
    # forward and backward
    for name, count in (("_conv_forward", 3), ("_conv_backward", 3),
                        ("_norm_forward", 1), ("_norm_backward", 1)):
        assert sum(f"jit({name})" in path for path in ours) == count, name
    assert len(moved) <= 1, moved
    assert stats.temp_size_in_bytes < 1.4e9, stats


# The causal attention's forward and backward kernels alone at the four
# causal cells' calls (a sequence at a time under ``lax.map``; sequences a
# step, positions, key-value heads, group, key width, value width), or the
# rule's refusal of the shape.
@pytest.mark.parametrize("plane", ["fused", "xla"])
def test_kda_block_compiles_for_v5e_with_its_state_in_vmem(one_chip, plane):
    """A KDA block at the published shapes (8,192 positions, groups of 8
    heads of 128, chunks of 64), forward and backward. On the kernels the
    delta rule's two ``pallas_call``s lie under ``lm_kda_scan`` (the head
    group's rematerialised forward, which writes the chunks' starting states,
    and the backward; nothing reads the first forward's output under a loss
    that is a sum) and the walk over the 128 chunks is no loop of the
    program's any more; the passes on either side of it
    (``ops/pallas_kda_passes.py``) lie under ``lm_kda_passes`` and outside
    ``lm_kda_scan``: four parts of the joined projection's output and the
    output norm, rematerialised forward and backward each, ``u``'s gradient
    handed on from part to part and never joined by a copy. On ``jax.numpy``
    the walk is two loops (forward and backward) and no kernel is called."""
    import re

    from multiverso_tpu.models.hybrid_lm.model import kda_passes_blocks
    cfg = HybridLMConfig.from_file(os.path.join(
        ROOT, "benchmark", "configs", LING3 + ".json"))
    assert cfg.pattern[0] == "K" and SEQ // cfg.kda_chunk == 128
    assert kda_passes_blocks(cfg) == cfg.pattern.count("K") == 5

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p = {k: spec(s) for k, s in param_shapes(cfg)["layers"][0].items()}
    u = spec((1, SEQ, cfg.hidden_size))
    scan = {"mixer_interpret": False} if plane == "fused" else {}

    def loss(p, u):
        return jnp.sum(layer_forward("K", p, None, u, cfg, True, **scan)[0])

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(p, u).compile()
    text = compiled.as_text()
    walks = [path for line in text.splitlines() if " while(" in line
             for path in [re.search(r'op_name="([^"]*)"', line).group(1)]
             if "lm_kda_scan" in scope_names(path)]
    calls = _kernel_calls(text)
    stats = compiled.memory_analysis()
    print(f"KDA block, {plane}: temporaries "
          f"{stats.temp_size_in_bytes / 1e9:.3f} GB, code "
          f"{stats.generated_code_size_in_bytes / 1e6:.1f} MB")
    assert "lm_kda_passes" in _scopes_of(compiled)
    if plane == "xla":
        assert len(walks) == 2 and not calls
        assert stats.temp_size_in_bytes < 2.0e9
        return
    assert not walks
    scan_calls = [path for path in calls
                  if "lm_kda_scan" in scope_names(path)]
    assert sorted(path.split("/")[-2] for path in scan_calls) == [
        "jit(_backward)", "jit(_forward)"]
    passes = [path for path in calls if path not in scan_calls]
    assert all("lm_kda_passes" in scope_names(path) for path in passes)
    # q, k, v and the gate; the output norm
    for name, count in (("_inputs_forward", 4), ("_inputs_backward", 4),
                        ("_out_forward", 1), ("_out_backward", 1)):
        assert sum(f"jit({name})" in path for path in passes) == count, name
    assert len(passes) == 10
    # what still moves 128 MB or more: the joined weights' gradient cut into
    # its four leaves, once a block; nothing a position long
    assert not [m for m in _moved_whole(text, 128e6) if str(SEQ) in m[1]]
    assert stats.temp_size_in_bytes < 1.3e9


@pytest.mark.parametrize("seqs,length,kv_heads,group,width,value_width,taken", [
    (1, SEQ, 16, 1, 128, 128, True), (2, SEQ, 16, 1, 192, 128, True),
    (2, SEQ, 2, 16, 128, 128, True), (4, SEQ, 8, 4, 64, 64, False)],
    ids=["ouro_train", "dsv2lite_train", "nemotron_train", "lfm2_train"])
def test_attention_kernels_compile_for_v5e_at_the_cells_shapes(
        one_chip, seqs, length, kv_heads, group, width, value_width, taken):
    from multiverso_tpu.ops.pallas_causal_attention import (
        attention_kernel_selected, backward, forward)
    blk = 512
    assert attention_kernel_selected(length, blk, kv_heads, group, width,
                                     value_width, np.float32) == taken
    if not taken:
        return

    def spec(*shape):
        return jax.ShapeDtypeStruct((1, length // blk, blk) + shape,
                                    jnp.float32, sharding=one_chip)

    q, k, v = (spec(kv_heads, group, width), spec(kv_heads, width),
               spec(kv_heads, value_width))
    plane = dict(scale=width ** -0.5, blk=blk, span=None, interpret=False)
    walk = forward.lower(q, k, v, **plane).compile()
    assert walk.as_text().count("tpu_custom_call") == 1
    out, lse = (jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=one_chip)
                for t in jax.eval_shape(
                    lambda *a: forward(*a, **plane), q, k, v))
    pull = backward.lower(q, k, v, out, lse, out, **plane).compile()
    assert pull.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("config,kind,layer", [(OURO, "*", 0),
                                               (DSV2, "L", 0)])
def test_attention_block_compiles_for_v5e_with_its_pairs_in_vmem(
        one_chip, config, kind, layer):
    """An attention block as HybridLM runs it on one chip
    (``mixer_interpret=False``): the remat's forward and the backward are the
    only ``pallas_call``s, both under ``lm_attn_pairs``, and under that scope
    nothing rewrites a slice of a carry (the ``jax.numpy`` walk's ``dq``)."""
    cfg = HybridLMConfig.from_file(os.path.join(
        ROOT, "benchmark", "configs", config + ".json"))
    assert cfg.pattern[layer] == kind

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p = {k: spec(s) for k, s in param_shapes(cfg)["layers"][layer].items()}
    u = spec((1, SEQ, cfg.hidden_size))

    def scoped(plane):
        def loss(p, u):
            return jnp.sum(layer_forward(kind, p, None, u, cfg, True,
                                         **plane)[0])
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            p, u).compile().as_text()
        paths = parse_scopes(text)[1]
        return text, {name: path for name, path in paths.items()
                      if "lm_attn_pairs" in scope_names(path)}

    def rewrites(pairs):
        return [name for name, path in pairs.items()
                if "dynamic-update-slice" in name
                or "dynamic_update_slice" in path]

    text, pairs = scoped({"mixer_interpret": False})
    kernels = [path for path in parse_scopes(text)[1].values()
               if "pallas_call" in path]
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert kernels and all("lm_attn_pairs" in scope_names(path)
                           for path in kernels)
    assert not rewrites(pairs), rewrites(pairs)
    # the check finds on the walk what it is there to miss
    text, pairs = scoped({})
    assert "tpu_custom_call" not in text
    assert rewrites(pairs)


@pytest.mark.parametrize("tokens,planes", [(32768, 16), (16384, 21),
                                           (16384, 24)],
                         ids=["lfm2_train", "2688_columns", "nemotron_train"])
def test_unique_row_add_kernel_compiles_for_v5e(one_chip, tokens, planes):
    """The expert layer's DMA row kernel alone, at two cells' accumulators:
    Mosaic refuses a one-row slice of a float32 [tokens, columns] array
    wider than 128 columns ("Slice shape along dimension 0 must be aligned
    to tiling (8), but is 1") and takes the same data as [tokens, columns /
    128, 128], a row one whole plane: at 16 planes (2,048 columns), at the
    21 of 2,688 columns, and at the 24 the expert layer carries those as
    (whole tiles a row). The guard against the tiling's refusal coming
    back."""
    from multiverso_tpu.ops.pallas_rows import add_unique_rows

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    columns = planes * 128
    compiled = jax.jit(add_unique_rows, donate_argnums=0).lower(
        spec((tokens, planes, 128)), spec((512,), jnp.int32),
        spec((512, planes, 128))).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    # in place: the accumulator goes in and comes out in one buffer
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        tokens * columns * 4


def test_evabyte_step_compiles_for_v5e_inside_a_chips_memory(one_chip):
    """``evabyte_train``'s whole loss-and-gradient at the cell's size (820 M
    parameters, one sequence of 16,384 bytes, eight targets a position): its
    temporaries beside 3.29 GB of parameters, as much of gradients and as
    much again of accumulators have to stay under 16 GB. The feed-forward's
    four slabs are unrolled: under ``lax.map`` the same program read 13.7 GB
    of temporaries here, with no slab 5.7, unrolled 3.2."""
    from multiverso_tpu.models.hybrid_lm import make_loss
    cfg = HybridLMConfig.from_file(os.path.join(
        ROOT, "benchmark", "configs", EVABYTE + ".json"))

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        spec, param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    length, heads = 2 * SEQ, cfg.num_pred_heads
    compiled = jax.jit(jax.value_and_grad(
        make_loss(cfg), argnums=(0, 1), has_aux=True)).lower(
            params, spec((cfg.row_bucket, cfg.hidden_size)),
            [None] * len(cfg.pattern), spec((1, length), jnp.int32),
            spec((1, length, heads), jnp.int32),
            spec((1, length, heads))).compile()
    stats = compiled.memory_analysis()
    assert stats.argument_size_in_bytes > 3.28e9
    assert stats.temp_size_in_bytes < 4.0e9, stats
    # the TPU compiler keeps the program's scopes on its instructions
    # (telemetry/device_scopes.py reads them back from this text)
    assert {"lm_embed", "lm_head_loss", "lm_eva", "lm_eva_prep",
            "lm_eva_agg", "lm_dense_ffn"} <= _scopes_of(compiled)


def test_sala_step_compiles_for_v5e_inside_a_chips_memory(one_chip):
    """``sala_train``'s whole loss-and-gradient at the cell's size (one
    sequence of 16,384 tokens): a TPU program RESERVES its temporaries beside
    every live buffer (``peak_bytes_in_use`` does not count them), so what has
    to stay under the 15.75 GiB a v5e chip reports is parameters + gradients
    + accumulators + the embedding's rows and accumulator + the program's
    temporaries (the eight saved block inputs alone are 2.15 GB). With every
    head of every mixer that sum is 18.8 GB (PERF.md 4): the configuration
    holds half of each mixer's heads."""
    from multiverso_tpu.models.hybrid_lm import make_loss
    cfg = HybridLMConfig.from_file(os.path.join(
        ROOT, "benchmark", "configs", SALA + ".json"))

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        spec, param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    buffers = [None if b is None else spec(b.shape)
               for b in init_buffers(cfg)]
    length = 2 * SEQ
    compiled = jax.jit(jax.value_and_grad(
        make_loss(cfg), argnums=(0, 1), has_aux=True)).lower(
            params, spec((7 * cfg.row_bucket, cfg.hidden_size)), buffers,
            spec((1, length), jnp.int32), spec((1, length), jnp.int32),
            spec((1, length))).compile()
    stats = compiled.memory_analysis()
    plane = 4 * sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))
    table = 2 * 4 * cfg.vocab_size * cfg.hidden_size
    assert stats.argument_size_in_bytes > plane
    reserved = (stats.argument_size_in_bytes + stats.output_size_in_bytes
                + stats.temp_size_in_bytes + plane + table)
    assert reserved < 15.75 * 2 ** 30 - 0.5e9, (reserved, stats)
    assert {"lm_embed", "lm_head_loss", "lm_attention", "lm_sparse_select",
            "lm_sparse_attn", "lm_lightning", "lm_lightning_scan",
            "lm_dense_ffn"} <= _scopes_of(compiled)


def test_lfm2_step_compiles_for_v5e_inside_a_chips_memory(one_chip):
    """``lfm2_train``'s whole loss-and-gradient at the cell's size (4
    sequences of 8,192 tokens), the head TIED: the rows argument is the whole
    16,384-row slice, there is no ``head`` leaf, and the rows' gradient comes
    back beside the parameters'. What has to stay under the 15.75 GiB a v5e
    chip reports is parameters + gradients + accumulators + the table's rows
    and accumulator + the program's temporaries (7.3 GB here, of which the
    twelve saved block inputs are 3.2). The expert blocks as ``HybridLM``
    runs them on one chip, their rows landing by the DMA row kernel (ISSUE
    41): eight kernel instances, the forward's and the backward's of four
    blocks (a rematerialised forward's result nobody reads, and it goes), no
    scatter into a [tokens, hidden] accumulator, the temporaries what they
    were (7.381 GB on both planes)."""
    from multiverso_tpu.models.hybrid_lm import dense_param_count, make_loss
    cfg = HybridLMConfig.from_file(os.path.join(
        ROOT, "benchmark", "configs", LFM2 + ".json"))

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = param_shapes(cfg)
    assert "head" not in shapes and cfg.tie_word_embeddings
    params = jax.tree_util.tree_map(
        spec, shapes, is_leaf=lambda x: isinstance(x, tuple))
    buffers = [None if b is None else spec(b.shape)
               for b in init_buffers(cfg)]
    compiled = jax.jit(jax.value_and_grad(
        make_loss(cfg, moe_rows_interpret=False), argnums=(0, 1),
        has_aux=True)).lower(
            params, spec((cfg.vocab_size, cfg.hidden_size)), buffers,
            spec((4, SEQ), jnp.int32), spec((4, SEQ), jnp.int32),
            spec((4, SEQ))).compile()
    _experts_on_the_row_kernel(compiled)
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 8
    stats = compiled.memory_analysis()
    plane = 4 * dense_param_count(cfg)
    table = 2 * 4 * cfg.vocab_size * cfg.hidden_size
    assert plane == 4 * 535_093_376
    # the parameters and the slice's rows go in, their gradients come out
    assert stats.argument_size_in_bytes > plane + table // 2
    assert stats.output_size_in_bytes > plane + table // 2
    reserved = (stats.argument_size_in_bytes + stats.output_size_in_bytes
                + stats.temp_size_in_bytes + plane + table)
    assert reserved < 15.75 * 2 ** 30 - 0.5e9, (reserved, stats)
    assert {"lm_embed", "lm_head_loss", "lm_shortconv", "lm_attention",
            "lm_dense_ffn", "lm_experts"} <= _scopes_of(compiled)


def test_ouro_step_compiles_for_v5e_inside_a_chips_memory(one_chip):
    """``ouro_train``'s whole loss-and-gradient at the cell's size (409 M
    dense parameters, one sequence of 8,192 tokens, the six layers run four
    times in ONE rolled loop, four passes' logits over 49,152 ids): what has
    to stay under the 15.75 GiB a v5e chip reports is parameters + gradients
    + accumulators + the table's rows and accumulator + the program's
    temporaries (7.8 GB here, of which the 12 x 4 saved block inputs are
    3.2). The blocks lie inside the loop's scope, the gate and the head
    outside it, and the program holds the stack once: it is a ``while``, not
    four copies."""
    from multiverso_tpu.models.hybrid_lm import dense_param_count, make_loss
    cfg = HybridLMConfig.from_file(os.path.join(
        ROOT, "benchmark", "configs", OURO + ".json"))
    assert cfg.total_ut_steps == 4 and cfg.pattern == "*D" * 6

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        spec, param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    compiled = jax.jit(jax.value_and_grad(
        make_loss(cfg), argnums=(0, 1), has_aux=True)).lower(
            params, spec((5 * cfg.row_bucket, cfg.hidden_size)),
            [None] * len(cfg.pattern), spec((1, SEQ), jnp.int32),
            spec((1, SEQ), jnp.int32), spec((1, SEQ))).compile()
    stats = compiled.memory_analysis()
    plane = 4 * dense_param_count(cfg)
    table = 2 * 4 * cfg.vocab_size * cfg.hidden_size
    assert plane == 4 * 408_997_889
    assert stats.argument_size_in_bytes > plane
    assert stats.output_size_in_bytes > plane
    assert 48 * SEQ * cfg.hidden_size * 4 < stats.temp_size_in_bytes < 8.5e9
    reserved = (stats.argument_size_in_bytes + stats.output_size_in_bytes
                + stats.temp_size_in_bytes + plane + table)
    assert reserved < 15.75 * 2 ** 30 - 0.5e9, (reserved, stats)
    text = compiled.as_text()
    paths = parse_scopes(text)[1].values()
    assert {"lm_embed", "lm_loop", "lm_loop_norm", "lm_exit_gate",
            "lm_head_loss", "lm_attention", "lm_dense_ffn"} <= {
                name for path in paths for name in scope_names(path)}
    assert all("lm_loop" in scope_names(path) for path in paths
               if "lm_attention" in scope_names(path))
    # rolled: the twelve blocks once in the forward and once in the backward
    assert " while(" in text


def test_ling3_step_compiles_for_v5e_inside_a_chips_memory(one_chip):
    """``ling3_train``'s whole loss-and-gradient at the cell's size (2
    sequences of 8,192 tokens, 657 M dense parameters with 8 held experts a
    block): parameters + gradients + accumulators + the table's rows and
    accumulator + the program's temporaries stay under the 15.75 GiB a v5e
    chip reports (15.2 GB; with ISSUE 48's first choice of 16 held experts
    17.3, which is why the file states the fallback: PERF.md 3). The
    temporaries are 6.5-7.1 GB whatever the experts held; a KDA block's heads
    go a group of 8 at a time (all 32 at once: 9.7 GB). The latent-attention block
    runs on the attention kernels, the expert blocks on the row kernel and
    the KDA blocks' delta rule on its kernels (PR 49), as ``HybridLM`` runs
    them on one chip; a KDA block's forward kernel is in the program TWICE
    (the forward pass; the head group's rematerialisation, which writes the
    chunks' starting states: the layer's own rematerialised copy has no
    reader and is dropped) beside one backward."""
    from multiverso_tpu.models.hybrid_lm import dense_param_count, make_loss
    cfg = HybridLMConfig.from_file(os.path.join(
        ROOT, "benchmark", "configs", LING3 + ".json"))
    assert cfg.pattern == "KDKDKEKEKELE" and len(cfg.held) == 8

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        spec, param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    buffers = [None if b is None else spec(b.shape)
               for b in init_buffers(cfg)]
    compiled = jax.jit(jax.value_and_grad(
        make_loss(cfg, moe_rows_interpret=False, mixer_interpret=False),
        argnums=(0, 1), has_aux=True)).lower(
            params, spec((10 * cfg.row_bucket, cfg.hidden_size)), buffers,
            spec((2, SEQ), jnp.int32), spec((2, SEQ), jnp.int32),
            spec((2, SEQ))).compile()
    _experts_on_the_row_kernel_among_others(compiled, {"lm_mla", "lm_kda"})
    in_kda = [path for path in _kernel_calls(compiled.as_text())
              if "lm_kda" in scope_names(path)]
    walks = [path for path in in_kda if "lm_kda_scan" in scope_names(path)]
    blocks = cfg.pattern.count("K")
    assert sum("jit(_backward)" in path for path in walks) == blocks
    assert sum("jit(_forward)" in path for path in walks) == 2 * blocks
    # the passes on either side of the rule (PR 51), forward twice as the rule
    passes = [path for path in in_kda if path not in walks]
    assert all("lm_kda_passes" in scope_names(path) for path in passes)
    for name, count in (("_inputs_forward", 8), ("_inputs_backward", 4),
                        ("_out_forward", 2), ("_out_backward", 1)):
        assert sum(f"jit({name})" in path
                   for path in passes) == count * blocks, name
    stats = compiled.memory_analysis()
    print(f"ling3 step: temporaries {stats.temp_size_in_bytes / 1e9:.3f} GB, "
          f"code {stats.generated_code_size_in_bytes / 1e6:.1f} MB")
    plane = 4 * dense_param_count(cfg)
    table = 2 * 4 * cfg.vocab_size * cfg.hidden_size
    assert plane == 4 * 657_397_536
    assert stats.argument_size_in_bytes > plane
    assert stats.output_size_in_bytes > plane
    assert 12 * 2 * SEQ * cfg.hidden_size * 4 < stats.temp_size_in_bytes \
        < 7.5e9
    reserved = (stats.argument_size_in_bytes + stats.output_size_in_bytes
                + stats.temp_size_in_bytes + plane + table)
    # the jax.numpy delta rule's program reserved 15.17-15.53 GB (PR 48)
    assert reserved < 15.2e9, (reserved, stats)
    # the five equal KDA blocks share their code (68.6 MB; 79.0 before the
    # passes' kernels): a program that stops sharing it reads twice that, and
    # ``peak_hbm_gb`` counts it (PR 47: +1.5%, past the bound)
    assert stats.generated_code_size_in_bytes < 90e6, stats
    assert {"lm_embed", "lm_head_loss", "lm_kda", "lm_kda_scan", "lm_mla",
            "lm_dense_ffn", "lm_experts", "lm_route"} <= _scopes_of(compiled)


def test_nemotron_step_on_the_row_kernel_keeps_its_program_small(one_chip,
                                                                 cfg):
    """``nemotron_train``'s whole loss-and-gradient with its expert blocks'
    rows landing by the DMA row kernel (ISSUE 41). Its 2,688 columns are 21
    planes: carried as 21, XLA re-laid [tokens, 21, 128] through a
    transposed layout and the executable's code read 211 MB where it reads
    56 (the chip showed it as +1.9% ``peak_hbm_gb``, past the cell's bound);
    carried as 24, columns padded before the view and cut after it, the
    program is the size it was."""
    from multiverso_tpu.models.hybrid_lm import make_loss

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        spec, param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    buffers = [None if b is None else spec(b.shape)
               for b in init_buffers(cfg)]
    compiled = jax.jit(jax.value_and_grad(
        make_loss(cfg, moe_rows_interpret=False), argnums=(0, 1),
        has_aux=True)).lower(
            params, spec((7 * cfg.row_bucket, cfg.hidden_size)), buffers,
            spec((2, SEQ), jnp.int32), spec((2, SEQ), jnp.int32),
            spec((2, SEQ))).compile()
    _experts_on_the_row_kernel(compiled)
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 8
    stats = compiled.memory_analysis()
    assert stats.generated_code_size_in_bytes < 100e6, stats
    assert stats.temp_size_in_bytes < 6.5e9, stats


def test_nemotron_step_on_its_kernels_shares_its_blocks_code(one_chip, cfg):
    """``nemotron_train``'s loss-and-gradient as HybridLM builds it on a chip
    (every kernel compiled), under the compiler options HybridLM gives its
    delta program: the pattern's equal blocks share their code. Without the
    option the TPU compiler shares it only when memory is short, which with
    the Mamba-2 blocks' fused passes (temporaries 4.5 GB where they were 6.3)
    it no longer is: the executable then reads 165 MB of code here and on the
    chip, where it read 46 (``peak_hbm_gb`` +1.5%, past the cell's bound)."""
    import types

    from multiverso_tpu.models.hybrid_lm import HybridLM, make_loss

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    options = HybridLM._delta_options(types.SimpleNamespace(
        mixer_interpret=False, cfg=cfg))
    assert options
    params = jax.tree_util.tree_map(
        spec, param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    buffers = [None if b is None else spec(b.shape)
               for b in init_buffers(cfg)]
    stats = jax.jit(jax.value_and_grad(
        make_loss(cfg, moe_rows_interpret=False, mixer_interpret=False),
        argnums=(0, 1), has_aux=True)).lower(
            params, spec((7 * cfg.row_bucket, cfg.hidden_size)), buffers,
            spec((2, SEQ), jnp.int32), spec((2, SEQ), jnp.int32),
            spec((2, SEQ))).compile(
                compiler_options=options).memory_analysis()
    assert stats.generated_code_size_in_bytes < 80e6, stats
    assert stats.temp_size_in_bytes < 5.0e9, stats


# (tables, rows a table, width, ids a table, one [B, n] id matrix?): the two
# cells whose steps keep their pulled rows on the device (ISSUE 27).
@pytest.mark.parametrize("tables,rows,width,ids,matrix", [
    (26, 262144, 128, 2048, True), (1, 16384, 2688, 7168, False)],
    ids=["dlrm_train", "nemotron_train"])
def test_device_form_group_programs_compile_for_v5e(one_chip, tables, rows,
                                                    width, ids, matrix):
    """The grouped gather that hands out device rows and the grouped AdaGrad
    update that takes device deltas, at the cells' sizes: the [B, n, D]
    matrix layout (DLRM) and the one-block-a-table layout (the LM), whose
    programs take and return the members' blocks as a tuple."""
    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    updater = get_updater(np.float32, "adagrad")
    state = jax.eval_shape(
        lambda: updater.init_state((rows, width), jnp.float32, 1))
    datas = (spec((rows, width)),) * tables
    states = (jax.tree_util.tree_map(lambda s: spec(s.shape, s.dtype),
                                     state),) * tables
    lengths = None if matrix else (ids,) * tables
    id_spec = spec((ids, tables) if matrix else (ids * tables,), jnp.int32)
    deltas = spec((ids, tables, width)) if matrix else \
        (spec((ids, width)),) * tables
    access = build_group_access(
        [lambda data, i: jnp.take(data, i, axis=0, mode="clip")] * tables)
    # Each on the row plane a one-chip store of its shape picks: the fused
    # Pallas kernel at 128 columns (DLRM), XLA at 2,688 (the LM).
    fused = fused_rows_selected(updater, (rows, width), np.float32, True,
                                False)
    assert fused == (width == 128)
    update = build_group_update([build_row_update(updater, fused)] * tables)
    pulled = access.lower(datas, id_spec, lengths=lengths,
                          blocks=not matrix).compile()
    assert pulled.memory_analysis().output_size_in_bytes >= \
        ids * tables * width * 4
    pushed = update.lower(datas, states, id_spec, deltas,
                          *group_scalars([AddOption()] * tables),
                          lengths=lengths).compile()
    # donated: the tables and their accumulators are updated in place
    assert pushed.memory_analysis().alias_size_in_bytes >= \
        2 * tables * rows * width * 4


def test_word2vec_fused_block_program_compiles_for_v5e(one_chip):
    """``w2v_train``'s block program at the cell's own size (four tables of
    4,000,000 x 128 float32, chunks of 8,192 pairs, K=5) with the chunk
    loop's row updates on the Pallas plane (ISSUE 31): Mosaic takes the
    three kernels (``both`` for w_in's 8,192 ids, ``accumulate`` and
    ``step`` for w_out's 49,152), the tables are updated in place, and no
    table-sized copy sits in the loop (2 GB a chunk each would)."""
    from multiverso_tpu.models.word2vec.model import (_make_block_fn,
                                                      _on_row_kernel)
    V, D, S, L = 4_000_000, 128, 512, 512

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = _on_row_kernel(_make_block_fn(window=5, negative=5, chunk=8192,
                                       adagrad=True, compact=True), False)
    compiled = jax.jit(fn, donate_argnums=(0, 1, 2, 3)).lower(
        *[spec((V, D))] * 4, spec((1 << 20,), jnp.int32), spec((V,)),
        spec((S, L), jnp.int32), spec((S,), jnp.int32),
        spec((2,), jnp.uint32), spec(())).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    assert not [line for line in text.splitlines()
                if " copy(" in line and f"f32[{V},{D}]" in line]
    assert {"w2v_pairs", "w2v_gather", "w2v_grads", "w2v_rows",
            "w2v_rows_in", "w2v_rows_out"} <= _scopes_of(compiled)
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes >= 4 * V * D * 4
    # a block's pair streams and a chunk's sorted planes (1.37 GB, 9 MB
    # under the XLA plane's program), and no table (2.05 GB) beside them
    assert stats.temp_size_in_bytes < 1.5e9, stats


def test_word2vec_mesh_block_program_compiles_for_four_v5e(topo):
    """``w2v_train_x4``'s block program at the cell's own size (four tables
    of 8,000,000 x 128 float32 in row ranges over ``model`` of a 2 x 2 mesh,
    chunks of 8,192 pairs, K=5) with the row kernel a shard (ISSUE 33): the
    plane is read off the layout, Mosaic takes the three kernels under
    ``shard_map``, every shard is updated in place, no shard-sized copy sits
    in the program, and the row update adds no collective of table rows."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from multiverso_tpu.models.word2vec.model import (
        _ShardedRows, build_sharded_block_step)
    V, D, S, L = 8_000_000, 128, 512, 512
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))

    def spec(shape, dtype=jnp.float32, p=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, p))

    step = build_sharded_block_step(mesh, window=5, negative=5, chunk=8192,
                                    adagrad=True, compact=True)
    args = (*[spec((V, D), p=P("model", None))] * 4,
            spec((1 << 20,), jnp.int32), spec((V,)),
            spec((S, L), jnp.int32, P("data", None)),
            spec((S,), jnp.int32, P("data")), spec((2,), jnp.uint32),
            spec(()))
    program, plane = step.program(*args)
    assert plane == _ShardedRows(mesh, "model", False)
    compiled = program.lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    assert not [line for line in text.splitlines()
                if " copy(" in line and f"f32[{V // 2},{D}]" in line]
    # the kernels a shard and the gathers' all-reduce are owned
    owned = parse_scopes(text)[1]
    kernels = [path for name, path in owned.items()
               if name.startswith("shard_map")]
    assert len(kernels) == 3 and all("/w2v_rows/" in p for p in kernels)
    assert any("/w2v_gather/" in path for name, path in owned.items()
               if name.startswith("all-reduce"))
    stats = compiled.memory_analysis()     # of one chip
    assert stats.alias_size_in_bytes >= 4 * (V // 2) * D * 4
    assert stats.temp_size_in_bytes < 1.5e9, stats
