"""hlocheck fixture: hlo-collective-budget — a shard_map psum whose
compiled all-reduce is missing from the declared budget (the GSPMD-
reshard-regression shape: the program communicates more than its
declaration admits), plus the correctly budgeted case."""

from copilot_for_consensus_tpu.analysis.contracts import (
    ContractCase,
    HloSpec,
    contract,
    require_devices,
)


def _case(budget):
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
        return jax.lax.psum(x, "sp")

    fn = shard_map(body, mesh=mesh, in_specs=(P("sp"),), out_specs=P())
    return ContractCase(
        fn=fn, args=(jax.ShapeDtypeStruct((8,), jnp.float32),),
        mesh=mesh,
        hlo=HloSpec(collectives=budget))


def bad_budget():
    return _case({})              # the psum's all-reduce is undeclared


def good_budget():
    return _case({"all-reduce": 1})


SHARDCHECK_CONTRACTS = [
    contract("bad_budget", bad_budget),
    contract("good_budget", good_budget),
]
