"""The program names its own layers where the work happens.

Compiled programs carry the oracle's sub-layers in their ops' `op_name`
metadata (`.../oracle.gather/L<width>/...`, `oracle.scatter`,
`oracle.project`, `oracle.kernel`, and `power_iteration` around the power
steps), which a profiler trace shows as each op's `tf_op`; the Pallas
kernels carry their names and metadata into the op's HLO text; and the
host ingest records `bucketize` and `normalize_rows` spans.  The kernels'
names, compiled for a described chip, are checked in tests/test_tpu_compile.py.
The catalog is docs/observability.md.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro import telemetry
from repro.core import (
    DistConfig,
    DistributedMaximizer,
    MatchingObjective,
    MaximizerConfig,
    normalize_rows,
)
from repro.instances import bucketize
from repro.instances.generator import (
    MatchingInstanceSpec,
    generate_matching_instance,
)
from repro.launch.mesh import data_mesh

SPEC = MatchingInstanceSpec(num_sources=800, num_destinations=16, avg_degree=5.0)


@pytest.fixture(scope="module")
def scaled():
    return normalize_rows(bucketize(generate_matching_instance(SPEC)))[0]


def op_names(text: str, opcode: str) -> list:
    """The `op_name` of every `opcode` op in compiled HLO text."""
    return re.findall(rf" {opcode}\(.*?op_name=\"([^\"]*)\"", text)


def _objective_calculate(inst):
    obj = MatchingObjective(inst)
    return jax.jit(obj.calculate).lower(
        jnp.zeros((inst.dual_dim,), jnp.float32), jnp.float32(0.1)
    )


def _objective_power(inst):
    obj = MatchingObjective(inst)
    return jax.jit(obj.power_iteration, static_argnums=1).lower(
        jax.random.key(0), 3
    )


def _distributed(program):
    def lower(inst):
        dm = DistributedMaximizer(
            inst, data_mesh(1),
            MaximizerConfig(gammas=(1.0, 0.1), iters_per_stage=4, power_iters=3),
            DistConfig(axes="data"),
        )
        sds = inst.shape_dtype_structs()
        lam = jax.ShapeDtypeStruct((inst.dual_dim,), jnp.float32)
        scalar = jax.ShapeDtypeStruct((), jnp.float32)
        with jax.set_mesh(dm.mesh):
            if program == "stage":
                return dm._stage_fn.lower(lam, scalar, scalar, sds)
            if program == "power":
                return dm._power_fn.lower(lam, sds)
            return dm._final_fn.lower(lam, scalar, sds)

    return lower


# program -> (its lowering, whether it projects, whether it is power steps)
PROGRAMS = {
    "calculate": (_objective_calculate, True, False),
    "objective_power": (_objective_power, False, True),
    "stage": (_distributed("stage"), True, False),
    "power": (_distributed("power"), False, True),
    "final": (_distributed("final"), True, False),
}


@pytest.mark.parametrize("program", list(PROGRAMS))
def test_compiled_ops_carry_oracle_scopes(scaled, program):
    lower, projects, power = PROGRAMS[program]
    text = lower(scaled).compile().as_text()
    widths = {f"L{b.idx.shape[1]}" for b in scaled.buckets}
    gathers, scatters = op_names(text, "gather"), op_names(text, "scatter")
    sorts = op_names(text, "sort")
    assert gathers and scatters
    assert bool(sorts) == projects
    for names, scope in ((gathers, "oracle.gather"),
                         (scatters, "oracle.scatter"),
                         (sorts, "oracle.project")):
        found = {re.search(rf"{scope}/(L\d+)/", n) for n in names}
        assert None not in found, (scope, names)
        # every bucket's gather and scatter-add is named with its width
        if scope != "oracle.project":
            assert {m.group(1) for m in found} == widths
    for n in gathers + scatters + sorts:
        assert ("power_iteration/" in n) == power, n


def test_host_ingest_spans():
    tracer = telemetry.Tracer()
    prev = telemetry.set_tracer(tracer)
    try:
        normalize_rows(bucketize(generate_matching_instance(SPEC)))
    finally:
        telemetry.set_tracer(prev)
    names = [e["name"] for e in tracer.events()]
    assert names.count("bucketize") == 1
    assert names.count("normalize_rows") == 1
    assert all(e["dur"] >= 0 for e in tracer.events())


def test_compile_cache_keys_on_op_names(tmp_path):
    """A program served from the persistent compilation cache keeps the op
    names it is compiled under, not those of a program cached under other
    names (core/objective.py keys the cache on op metadata)."""
    from jax.experimental.compilation_cache import compilation_cache

    def named(scope):
        def f(x):
            with jax.named_scope(scope):
                return jnp.sin(x) * 2.0

        return jax.jit(f).lower(jnp.ones((64,), jnp.float32))

    saved = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        compilation_cache.reset_cache()
        assert "old_name/" in named("old_name").compile().as_text()
        text = named("new_name").compile().as_text()
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert "new_name/" in text and "old_name/" not in text
