"""Compile a cell's programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 bench/aot.py <cell>

For the cell's configuration and traffic at their real sizes: the jitted
loader, the five sub-round programs and the version mover, on one chip or
over a described 2x2 mesh, each printed with XLA's ``memory_analysis`` (the
bytes on each device). Nothing runs, so this says nothing about results or
times; it finds what the chip's compiler would refuse at no chip time.
"""
from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (AxisType, NamedSharding,  # noqa: E402
                          PartitionSpec as P, SingleDeviceSharding)

import adapter  # noqa: E402
import spec  # noqa: E402
import traffic  # noqa: E402


def main(cell_name: str) -> None:
    from jax.experimental import topologies
    # a described chip's programs cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cell = spec.cell(cell_name)
    sizes = spec.config(cell.config)
    mix = traffic.load_mix(spec.traffic_path(cell.traffic))
    mesh = None
    if cell.chips > 1:
        mesh = jax.make_mesh((cell.chips,), ("mem",),
                             axis_types=(AxisType.Auto,),
                             devices=topo.devices[:cell.chips])
    drv = adapter.build(sizes, mix, cell.chips, mesh=mesh)
    cfg = drv.cfg
    one = SingleDeviceSharding(topo.devices[0])
    put = lambda tree, sh: jax.tree.map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), tree)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    report(f"{cell_name} loader", adapter.loader(drv), key)
    st = jax.eval_shape(adapter.loader(drv), jax.random.PRNGKey(0))
    inp = jax.eval_shape(lambda k: traffic.draw(
        k, mix, cfg.n_threads, cfg.n_warehouses, cfg.n_items,
        cfg.customers_per_district), jax.random.PRNGKey(0))
    if mesh is None:
        home = one
        st = put(st, one)
    else:
        home = NamedSharding(mesh, P())
        parted = NamedSharding(mesh, P("mem"))
        n = -(-drv.n_records // cell.chips) * cell.chips
        table = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            (n,) + s.shape[1:], s.dtype, sharding=parted), st.nam.table)
        nv = -(-cfg.n_threads // cell.chips) * cell.chips
        vec = jax.ShapeDtypeStruct((nv,), jnp.uint32, sharding=parted)
        st = put(st, home)
        st = st._replace(nam=st.nam._replace(
            table=table, oracle_state=st.nam.oracle_state._replace(vec=vec)))
    act = jax.ShapeDtypeStruct((cfg.n_threads,), jnp.bool_, sharding=home)
    rno = jax.ShapeDtypeStruct((), jnp.int32, sharding=home)
    progs = adapter.programs(drv)
    for kind, prog in progs.items():
        if kind == "version_mover":
            report(f"{cell_name} {kind}", prog, st.nam.table)
            continue
        tin = program_inputs(kind, inp[kind], home)
        if kind in adapter.READ_ONLY:
            report(f"{cell_name} {kind}", prog, st, tin, act)
        else:
            report(f"{cell_name} {kind}", prog, st, tin, rno, act, None)


def program_inputs(kind, fields, sharding):
    """One type's inputs as the engine's input record of that type."""
    return adapter.inputs_record(kind, {k: jax.ShapeDtypeStruct(
        v.shape, v.dtype, sharding=sharding) for k, v in fields.items()})


def report(name, fn, *args):
    t = time.perf_counter()
    compiled = fn.lower(*args).compile()
    m = compiled.memory_analysis()
    print(f"{name}: compiled in {time.perf_counter() - t:.1f} s; per device:"
          f" arguments {m.argument_size_in_bytes} B, outputs "
          f"{m.output_size_in_bytes} B, aliased {m.alias_size_in_bytes} B, "
          f"temporaries {m.temp_size_in_bytes} B", flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
