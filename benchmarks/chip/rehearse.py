"""Compile each cell's decode and prefill programs, at the cell's real
widths and serving shape, for one chip of a described TPU v5e; nothing
runs and no chip is needed. Prints each program's `memory_analysis` and
how many Mosaic kernel sites it holds.

    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse.py granite-3-2b.decode

The programs are the engine's own step functions
(`state_store.make_step_fns`), given abstract parameters (the augmented
PSpec tree) and the paged pool's arena shapes, so no weight or pool is
allocated. Kernel calls are compiled with Mosaic, as on the chip.
"""
from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")


def rehearse(name: str, topo) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    import cell
    import harness
    from repro.configs.base import ShapeConfig
    from repro.distributed.sharding import Rules
    from repro.kernels import ops
    from repro.launch.mesh import make_local_mesh
    from repro.models import augment
    from repro.models import model as M
    from repro.models.params import to_shape_dtype
    from repro.serve import state_store
    from repro.serve.cache_pool import PageGeometry

    # the kernels ask the default backend (the CPU here) whether to
    # interpret; the described chip compiles them with Mosaic
    ops._auto_interpret = lambda interpret: False
    c = cell.load(name)
    cfg = harness.model_config(c)
    B, S = c.shape["max_batch"], c.shape["max_seq"]
    mesh = make_local_mesh(topo.devices[:c.chips])
    on_mesh = NamedSharding(mesh, PartitionSpec())
    rules = Rules.make(mesh, cfg, ShapeConfig("serve", S, B, "decode"))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on_mesh)

    params = jax.tree.map(lambda s: sds(s.shape, s.dtype), to_shape_dtype(
        augment.augment_pspecs(cfg, M.abstract_params(cfg))))
    a = cfg.amc
    g = PageGeometry(cfg.n_layers, cfg.n_kv_heads, cfg.hd, a.page_size,
                     a.aug_bits)
    max_p = -(-S // a.page_size)
    n_normal = 1 if a.resolved_pool_mode == "always-augmented" \
        else B * max_p + 1
    n_packed = B * max_p + 1
    L, KV, P = g.n_layers, g.kv_heads, g.page_size
    packed_dt = jnp.uint8 if g.aug_bits == 4 else jnp.int8
    arenas = {"kn": sds((L, n_normal, KV, P, g.head_dim), jnp.bfloat16),
              "vn": sds((L, n_normal, KV, P, g.head_dim), jnp.bfloat16),
              "kp": sds((L, n_packed, KV, P, g.d_store), packed_dt),
              "vp": sds((L, n_packed, KV, P, g.d_store), packed_dt),
              "ks": sds((L, n_packed, KV, P), jnp.bfloat16),
              "vs": sds((L, n_packed, KV, P), jnp.bfloat16)}
    tables = {k: sds((B, max_p), jnp.int32) for k in
              ("page_table", "page_modes", "normal_idx", "packed_idx")}
    fns = state_store.make_step_fns(cfg, None, rules=rules)
    chunk = min(32, S)
    for prog, width in (("decode", 1), ("prefill", chunk)):
        batch = {**tables, "tokens": sds((B, width), jnp.int32),
                 "positions": sds((B,), jnp.int32),
                 "write_mask": sds((B,), jnp.bool_)}
        compiled = jax.jit(fns[prog], donate_argnums=(1,)).lower(
            params, arenas, batch).compile()
        mem = compiled.memory_analysis()
        print(f"{name} {prog}: compiled for {topo.devices[0].device_kind}; "
              f"{compiled.as_text().count('tpu_custom_call')} Mosaic kernel "
              f"sites; arguments {mem.argument_size_in_bytes} B, "
              f"temporaries {mem.temp_size_in_bytes} B, outputs "
              f"{mem.output_size_in_bytes} B, aliased "
              f"{mem.alias_size_in_bytes} B", flush=True)


def main() -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [SRC, HERE]
    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in sys.argv[1:]:
        rehearse(name, topo)


if __name__ == "__main__":
    main()
