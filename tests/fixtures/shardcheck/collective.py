"""shardcheck fixture: shard-collective — a shard_map body whose psum
names an axis the mesh it runs under does not have (caught at trace
time by eval_shape), plus the correctly bound body."""

from copilot_for_consensus_tpu.analysis.contracts import (
    ContractCase,
    contract,
    require_devices,
)


def _case(axis_name):
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from copilot_for_consensus_tpu.parallel.mesh import (
        MeshConfig,
        build_mesh,
    )

    require_devices(8)
    mesh = build_mesh(MeshConfig(sp=4), devices=jax.devices()[:8])

    def body(x):
        return jax.lax.psum(x, axis_name)

    fn = shard_map(body, mesh=mesh, in_specs=(P("sp"),), out_specs=P())
    return ContractCase(
        fn=fn, args=(jax.ShapeDtypeStruct((8,), jnp.float32),),
        mesh=mesh)


def bad_collective():
    return _case("model")       # no such axis on the sp mesh


def good_collective():
    return _case("sp")


SHARDCHECK_CONTRACTS = [
    contract("bad_collective", bad_collective),
    contract("good_collective", good_collective),
]
