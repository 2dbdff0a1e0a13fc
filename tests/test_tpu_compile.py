"""TPU compile rehearsals: the serve path's Pallas kernels, and one lm
bucket program, compiled for a described (not attached) v5e chip.

Interpret mode cannot see Mosaic's block-shape rules, and off a TPU the
dispatchers never pick the kernels at all; compiling for the described
chip is the check that costs no chip time. Nothing runs, so nothing here
says anything about results or times. The topology is described inside a
fixture (never at import), and every test of this kind lives in this one
file, so under several pytest workers only the worker given this file
loads the TPU compiler library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.batching import SufficientConditionPolicy
from repro.core.plan import BucketedPlanExecutor, CommitSpec, _BucketProgram
from repro.kernels.fused_gather_cell import fused_gather_lstm_cell_kernel
from repro.kernels.gather_batch import gather_rows_kernel
from repro.models.workloads import make_workload
from repro.serve.scheduler import RoundPlan, build_lm_feed_round_graph


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs under /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    compile for a described chip is written to it but cannot be read back
    without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _f32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


@pytest.mark.parametrize("D", [128, 512])
def test_gather_rows_kernel_compiles_for_v5e(one_chip, D):
    compiled = jax.jit(gather_rows_kernel).lower(
        _f32((64, D), one_chip), _i32((16,), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("H", [128, 512])
def test_fused_gather_lstm_cell_kernel_compiles_for_v5e(one_chip, H):
    E = H
    rows = [_f32((64, d), one_chip) for d in (E, H, H)]
    idx = [_i32((8,), one_chip)] * 3
    compiled = jax.jit(fused_gather_lstm_cell_kernel).lower(
        *rows, *idx, _f32((E + H, 4 * H), one_chip),
        _f32((4 * H,), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_lm_bucket_program_compiles_with_kernels(one_chip, monkeypatch):
    """The serve engine's lm bucket program at model_size 512 (the 8-entry
    feed round that warm-start compiles), with the dispatchers steered onto
    their TPU branch: the compiled text must hold the Pallas kernels."""
    wl = make_workload("ChainLM", 512)
    graph, _ = build_lm_feed_round_graph(RoundPlan(), count=8)
    pack = BucketedPlanExecutor(wl.impls, None, ladder=(8,)).pack_for(
        graph, SufficientConditionPolicy())
    prog = _BucketProgram(pack.spec, wl.impls)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    params = {"slots": {f: _f32((16, 512), one_chip)
                        for f in wl.state_fields}}
    idx = _i32((pack.spec.n_index_lanes,), one_chip)
    aux = _i32((pack.spec.n_aux_lanes,), one_chip)
    shapes = jax.eval_shape(lambda p, i, a: prog.body(p, i, a, {}),
                            params, idx, aux)
    pool = {k: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
            for k, s in shapes.items()}
    compiled = jax.jit(prog.body).lower(params, idx, aux, pool).compile()
    assert compiled.as_text().count("tpu_custom_call") > 0


def test_lm_committing_bucket_program_compiles_for_v5e(one_chip):
    """The serve engine's committing lm program at the benchmark cell's
    widths (hidden 650, vocab 10000, 64 entries): the round's commit stage
    (argmax, slot-pool scatter) compiles inside the bucket program."""
    from repro.models.chains import ChainLM
    from repro.serve.engine import _commit_stage

    wl = ChainLM(650, 1409, vocab=10000)
    graph, _ = build_lm_feed_round_graph(RoundPlan(), count=64)
    commit = CommitSpec("y", tuple(wl.state_fields), "R", _commit_stage)
    pack = BucketedPlanExecutor(wl.impls, None, ladder=(8,),
                                commit=commit).pack_for(
        graph, SufficientConditionPolicy())
    prog = _BucketProgram(pack.spec, wl.impls, commit=commit,
                          layout=pack.commit_layout)
    params = {"slots": {f: _f32((64, 650), one_chip)
                        for f in wl.state_fields}}
    idx = _i32((pack.spec.n_index_lanes,), one_chip)
    aux = _i32((pack.spec.n_aux_lanes,), one_chip)
    shapes = jax.eval_shape(lambda p, i, a: prog.body(p, i, a, {}),
                            params, idx, aux)
    pool = {k: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
            for k, s in shapes.items()}
    cidx = _i32(pack.commit_idx.shape, one_chip)
    compiled = jax.jit(prog.body_and_commit).lower(params, idx, aux, pool,
                                                   cidx).compile()
    toks, new_pools, _ = compiled.out_info
    assert toks.shape == (64,)
    assert {f: s.shape for f, s in new_pools.items()} == {
        f: (64, 650) for f in wl.state_fields}
