"""The main path's programs compile for a TPU v5e chip.

Each test lowers a program at the campaign's real shapes against a
described (not attached) ``v5e:2x2`` topology and compiles it with the
TPU compiler, which refuses what interpret mode accepts: unaligned block
shapes, too much VMEM, a program that does not fit the device.  Nothing
runs, so these say nothing about results or times.

The topology is described inside a fixture, never at import time: only
the worker that is handed this file loads the TPU library.
"""

import os
import re

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import aot
from repro.core import characterization as char
from repro.core import controller as ctl
from repro.core.accelerators import ACCELERATORS
from repro.kernels.grid_argmin import grid_argmin

# The campaign: 5 FPGA accelerators × 6 techniques × 15 scenarios.
CAMPAIGN_K = 5 * len(ctl.DEFAULT_TECHNIQUES) * 15


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without one; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _fpga_params():
    return char.stack_platform_params(
        [ctl.fpga_platform(a).params for a in ACCELERATORS.values()])


@pytest.mark.parametrize("n_nodes", [8, 64])
def test_grid_argmin_kernel_compiles(one_chip, n_nodes):
    """The Pallas kernel itself at the campaign's sweep (5 platforms ×
    12 rows × 25 bins at 8 nodes) and at a 64-gear sweep."""
    cfg = ctl.ControllerConfig(n_nodes=n_nodes)
    args = _on(one_chip, aot.tables_program_args(_fpga_params(), cfg))
    assert args[1].shape[0] == 4 + n_nodes      # DVFS rows + hybrid gears
    text = grid_argmin.lower(*args, impl="pallas").compile().as_text()
    assert "tpu_custom_call" in text


def test_tables_program_picks_kernel_for_tpu(one_chip):
    """With no explicit impl, the tables program lowered for a TPU runs
    the kernel, not the reference."""
    args = _on(one_chip, aot.tables_program_args(_fpga_params(),
                                                 ctl.ControllerConfig()))
    text = ctl._fleet_dvfs_tables_jit.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    # The kernel's pinned name, which the trace reduction looks for.
    assert re.search(r"%grid_argmin\.\d+ = .*custom-call\(", text)


def test_stream_chunk_program_compiles(one_chip):
    """The [K, C] chunk program at the campaign shape (K=450, C=1024,
    one tenant) fits one chip."""
    cfg = ctl.ControllerConfig()
    args = _on(one_chip, aot.stream_program_args(cfg, CAMPAIGN_K, 1024))
    compiled = ctl._fleet_stream_chunk_jit.lower(
        *args, ctl._runtime_cfg(cfg), ()).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def _while_body_instructions(text):
    """``(opcode, dims, operand dims)`` of every array instruction that
    the compiled program's while loops run, fused computations included.
    ``operand dims`` is the shape of the first operand, where the same
    computation defines it."""
    comps, lines = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", line)
        if head:
            lines = comps.setdefault(head.group(1), [])
        elif lines is not None and line.startswith("  "):
            lines.append(line)
    todo, seen = re.findall(r"body=%([\w.\-]+)", text), set()
    while todo:
        name = todo.pop()
        if name in comps and name not in seen:
            seen.add(name)
            todo += [c for line in comps[name]
                     for c in re.findall(r"calls=%([\w.\-]+)", line)]
    inst = re.compile(
        r"%([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(%?([\w.\-]*)")
    out = []
    for name in seen:
        dims = {}
        for line in comps[name]:
            m = inst.search(line)
            if m:
                dims[m.group(1)] = tuple(
                    int(d) for d in m.group(2).split(",") if d)
                out.append((m.group(3), dims[m.group(1)],
                            dims.get(m.group(4))))
    return out


@pytest.mark.parametrize("k, chunk", [(CAMPAIGN_K, 1024), (5000, 512)])
def test_chunk_program_moves_no_markov_state(one_chip, k, chunk):
    """Inside the chunk program's loop the Markov counts are read and
    updated in place: no scatter, no gather from an operand with the
    counts' M·M elements per cell, and no copy of the counts.  A
    row gather and an edge scatter want different layouts of the
    carried counts, and the compiler then relays the whole state out
    every step."""
    cfg = ctl.ControllerConfig()
    args = _on(one_chip, aot.stream_program_args(cfg, k, chunk))
    text = ctl._fleet_stream_chunk_jit.lower(
        *args, ctl._runtime_cfg(cfg), ()).compile().as_text()
    body = _while_body_instructions(text)
    state = k * cfg.n_bins ** 2

    def elements(dims):
        return None if dims is None else int(np.prod(dims))

    assert any(op == "fusion" for op, _, _ in body)
    assert not [i for i in body if i[0] == "scatter"]
    assert not [i for i in body
                if i[0] == "gather" and elements(i[2]) == state]
    assert not [i for i in body if i[0] in ("copy", "copy-start", "transpose")
                and elements(i[1]) == state]
