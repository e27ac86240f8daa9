"""Compiles for a described TPU v5e chip — no chip attached.

The TPU compiler is installed with JAX, so these tests hand it the fused
krylov-tick kernels and the engine's fleet programs at real sizes and
check what only the chip's compiler can refuse: Mosaic tiling and
fast-memory limits (the kernels must lower to a ``tpu_custom_call``) and
device memory (the fleet program at the one-chip ``chip_smoke.py`` size
must fit a v5e's HBM, and at four times that size over a 2x2 mesh each
chip must hold only its own quarter).  Nothing runs; no result or time
comes from here.

Only one process may load the TPU library, so the topology is described
inside a module fixture (never at import) and every such test lives in
this one file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                           SingleDeviceSharding)

V5E_HBM_BYTES = 16e9
SMOKE_STREAMS = 32_768          # chip_smoke.py's one-chip fleet size


@pytest.fixture(scope="module")
def v5e_devices():
    """The four devices of a described ``v5e:2x2`` topology, with JAX's
    persistent compile cache off (a compile for a described chip is
    written to it but cannot be read back without the chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR",
                                                    "disabled"))
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001 — no TPU library here
                pytest.skip(f"no v5e:2x2 topology can be described here: "
                            f"{e}")
        yield topo.devices
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def v5e_device(v5e_devices):
    return v5e_devices[0]


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _smoke_fleet(devices, streams, **hyper):
    """The engine-default ``"dsfd"`` fleet over ``devices``, and abstract
    state and slab placed as the engine places them."""
    from repro.sketch.api import make_sketch, shard_streams

    sk = make_sketch("dsfd", d=64, eps=1 / 8, window=1024, **hyper)
    fleet = shard_streams(sk, streams, Mesh(np.array(devices), ("streams",)))
    sharding = fleet.meta["slab_sharding"]
    state = jax.tree.map(lambda a: _spec(a.shape, a.dtype, sharding),
                         jax.eval_shape(lambda: fleet.init()))
    rows = _spec((streams, 8, 64), jnp.float32, sharding)
    return fleet, state, rows


@pytest.mark.parametrize("m,d", [(16, 64), (256, 300)])
@pytest.mark.parametrize("kernel", ["gram_power", "fused_krylov_step"])
def test_fused_tick_kernel_compiles_for_v5e(v5e_device, kernel, m, d):
    from repro.kernels.fused_tick import ops

    one = SingleDeviceSharding(v5e_device)
    D = _spec((m, d), jnp.float32, one)
    if kernel == "gram_power":
        fn = jax.jit(lambda D: ops.gram_power(D, interpret=False))
        args = (D,)
    else:
        fn = jax.jit(lambda D, lam, u: ops.fused_krylov_step(
            D, lam, u, interpret=False))
        args = (D, _spec((), jnp.float32, one), _spec((m,), jnp.float32, one))
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("hyper", [{}, {"mode": "krylov", "use_pallas": True}],
                         ids=["fast", "krylov-pallas"])
def test_smoke_fleet_program_fits_v5e_hbm(v5e_device, monkeypatch, hyper):
    """The engine's fleet ``update_block`` (``"dsfd"`` at the engine
    defaults, one-device ``shard_streams`` mesh) at the smoke's stream
    count: arguments + output + temporaries must fit a v5e's 16 GB.  Its
    temporaries are ~320 KB a stream against ~29 KB of state, so sizing
    the fleet from state bytes alone would not fit."""
    if hyper.get("use_pallas"):
        # off the chip ``auto`` lowers the kernel to its XLA ref; a trace
        # cached under that lowering would be reused, so drop the caches
        monkeypatch.setenv("REPRO_KERNEL_LOWERING", "pallas")
        jax.clear_caches()
    fleet, state, rows = _smoke_fleet([v5e_device], SMOKE_STREAMS, **hyper)
    ts = _spec((8,), jnp.int32, SingleDeviceSharding(v5e_device))
    compiled = jax.jit(fleet.update_block).lower(state, rows, ts).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, (
        f"{SMOKE_STREAMS} streams need {total / 1e9:.2f} GB "
        f"(arguments {mem.argument_size_in_bytes / 1e9:.2f}, temporaries "
        f"{mem.temp_size_in_bytes / 1e9:.2f}) > v5e HBM")
    if hyper.get("use_pallas"):
        assert "tpu_custom_call" in compiled.as_text()


def test_four_chip_fleet_program_holds_a_quarter_per_chip(v5e_devices):
    """``chip_smoke.py --chips 4``'s full-size fleet: 4 × the one-chip
    stream count over the 2x2 mesh.  Each chip's program must take only
    its own quarter of the state and slab (nothing replicated, nothing
    gathered onto one device), the in-place ``init`` must build only that
    quarter on each chip, and the whole must fit a v5e's HBM."""
    n = len(v5e_devices)
    fleet, state, rows = _smoke_fleet(v5e_devices, n * SMOKE_STREAMS)
    # a quarter, plus a little tile padding; a replicated leaf would
    # bring the state's whole size
    quarter = 1.05 * sum(a.size * a.dtype.itemsize
                         for a in jax.tree.leaves((state, rows))) / n
    ts = _spec((8,), jnp.int32,
               NamedSharding(rows.sharding.mesh, PartitionSpec()))
    mem = jax.jit(fleet.update_block).lower(
        state, rows, ts).compile().memory_analysis()
    assert mem.argument_size_in_bytes <= quarter, (
        f"each chip takes {mem.argument_size_in_bytes / 1e9:.3f} GB of "
        f"arguments, more than its quarter {quarter / 1e9:.3f} GB")
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, f"{total / 1e9:.2f} GB per chip"
    init = fleet.init.lower().compile().memory_analysis()
    state_quarter = 1.05 * sum(a.size * a.dtype.itemsize
                               for a in jax.tree.leaves(state)) / n
    assert init.output_size_in_bytes <= state_quarter, (
        f"init builds {init.output_size_in_bytes / 1e9:.3f} GB on a chip, "
        f"more than its quarter {state_quarter / 1e9:.3f} GB")


def test_update_program_for_v5e_keeps_the_dsfd_scopes(v5e_device):
    """The device trace's ops are attributed to DS-FD phases through the
    compiled program's ``op_name`` metadata: on the chip's compiler every
    loop of the update but the row scan itself, and every ``EighTpu`` of
    its SVDs, must still carry a ``dsfd.*`` scope."""
    import re

    fleet, state, rows = _smoke_fleet([v5e_device], 256)
    ts = _spec((8,), jnp.int32, SingleDeviceSharding(v5e_device))
    text = fleet.update_block.lower(state, rows, ts).compile().as_text()

    def scope(line):
        op = re.search(r'op_name="([^"]*)"', line)
        parts = op.group(1).split("/") if op else []
        return [p for p in parts if p.startswith("dsfd.")]

    loops = [ln for ln in text.splitlines() if " while(" in ln]
    unscoped = [ln.split(" = ")[0].strip() for ln in loops if not scope(ln)]
    assert len(loops) > 10 and len(unscoped) == 1, unscoped
    eigh = [ln for ln in text.splitlines() if '"EighTpu"' in ln]
    assert eigh and all(scope(ln)[-1] in ("dsfd.shrink", "dsfd.rotate")
                        for ln in eigh)
    found = {p for ln in text.splitlines() for p in scope(ln)}
    assert {"dsfd.absorb", "dsfd.dump", "dsfd.rotate", "dsfd.shrink",
            "dsfd.insert", "dsfd.expire", "dsfd.swap"} <= found
