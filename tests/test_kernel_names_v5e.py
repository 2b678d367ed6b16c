"""The tile kernel's and the cold band's kernel's names in a program
compiled for the chip: a described ``v5e:2x2`` (nothing attached, nothing
runs).  ``pl.pallas_call(name=...)`` renames the HLO instruction, and the
benchmark finds the kernels' device events by those names, so the names are
part of the yardstick.  Beside
them, what else only the chip's compiler can say: that the dense stripes stay
bandwidth-bound fusions, and that the random effect's Newton body fuses its
pairs Hessian.

The topology is described in a module-scoped fixture and nowhere at import:
only one process may load the TPU's library, and every xdist worker imports
every test file.  Keep such tests in this one file."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks import trace as trace_mod
from photon_ml_tpu.ops.sparse_pallas import (
    CODE_DTYPE,
    TILE_C,
    WIN,
    PallasSparseMatrix,
    _tiled_apply,
    build_pallas_host,
)

N_ROWS, N_COLS, NNZ = 4096, 3000, 40000


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def layout_shapes(one_chip):
    rng = np.random.default_rng(0)
    P = build_pallas_host(
        rng.integers(0, N_ROWS, NNZ), rng.integers(0, N_COLS, NNZ),
        rng.normal(size=NNZ).astype(np.float32), N_ROWS, N_COLS)
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        P)


def _kernel_calls(compiled_text):
    """Names of the custom calls that ``trace.is_kernel`` accepts."""
    lines = [ln.strip() for ln in compiled_text.splitlines()
             if trace_mod.KERNEL_OPCODE in ln]
    return [trace_mod.short_name(ln) for ln in lines
            if trace_mod.is_kernel(ln)]


@pytest.mark.parametrize("product, length, name", [
    ("matvec", N_COLS, "_tiled_apply_fwd"),
    ("rmatvec", N_ROWS, "_tiled_apply_bwd"),
    ("row_sq_matvec", N_COLS, "_tiled_apply_fwd"),
    ("sq_rmatvec", N_ROWS, "_tiled_apply_bwd"),
])
def test_kernel_instruction_names(monkeypatch, one_chip, layout_shapes,
                                  product, length, name):
    # Mosaic, not the interpreter: other test files set this for the process
    monkeypatch.delenv("PHOTON_PALLAS_INTERPRET", raising=False)
    vec = jax.ShapeDtypeStruct((length,), jnp.float32, sharding=one_chip)
    # the chip runs without x64 (tests/conftest.py turns it on), and Mosaic
    # takes no 64-bit scalar
    with jax.enable_x64(False):
        text = jax.jit(lambda P, v: getattr(P, product)(v)).lower(
            layout_shapes, vec).compile().as_text()
    calls = _kernel_calls(text)
    assert len(calls) == 1, calls
    stem, _, suffix = calls[0].rpartition(".")
    assert stem == name and suffix.isdigit()


def _pallas_calls(compiled_text):
    """Names of every Mosaic kernel's custom call, the cold band's too."""
    return [trace_mod.short_name(ln.strip())
            for ln in compiled_text.splitlines()
            if trace_mod.KERNEL_OPCODE in ln and "tpu_custom_call" in ln]


@pytest.fixture(scope="module", params=[False, True],
                ids=["full", "spill"])
def wide_shapes(request, one_chip):
    """A valued wide layout with both bands (its 2,048 most popular columns
    warm), shapes only: the cold band at its full depth (the spill priced
    out), or cut to 8 deep with the rest of its entries in the cold spill
    (the spill priced at nothing)."""
    from unittest import mock

    from photon_ml_tpu.ops import sparse_pallas as spl

    rng = np.random.default_rng(1)
    n, d = 2 * spl.COLD_TILE, 3 * spl.COLD_TILE
    rows = rng.integers(0, n, NNZ)
    cols = np.where(rng.random(NNZ) < 0.5, rng.integers(0, 2048, NNZ),
                    rng.integers(0, d, NNZ))
    warm = np.arange(2048)
    price = 0.0 if request.param else 1.0
    with mock.patch.object(spl, "_warm_prefix", lambda *_: warm), \
            mock.patch.multiple(spl, COLD_SPILL_FIXED_SECONDS=price,
                                COLD_SPILL_SECONDS=price):
        P = spl.build_wide_host(rows, cols, rng.normal(size=NNZ).astype(
            np.float32), n, d)
    assert P.has_warm and P.has_cold and not P.cold_unit
    assert P.cold_spill.has_spill is request.param
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        P)


@pytest.mark.parametrize("product, length, side", [
    ("matvec", 3 * 8192, "fwd"), ("rmatvec", 2 * 8192, "bwd"),
    ("row_sq_matvec", 3 * 8192, "fwd"), ("sq_rmatvec", 2 * 8192, "bwd"),
])
def test_wide_kernel_instruction_names(monkeypatch, one_chip, wide_shapes,
                                       product, length, side):
    """The wide layout's product is the warm band's tile kernel and the cold
    band's kernel, one each, under the names the benchmark's readers sum,
    with the cold spill's product beside them or not."""
    monkeypatch.delenv("PHOTON_PALLAS_INTERPRET", raising=False)
    vec = jax.ShapeDtypeStruct((length,), jnp.float32, sharding=one_chip)
    with jax.enable_x64(False):
        text = jax.jit(lambda P, v: getattr(P, product)(v)).lower(
            wide_shapes, vec).compile().as_text()
    stems = sorted(c.rpartition(".")[0] for c in _pallas_calls(text))
    assert stems == [f"_cold_apply_{side}", f"_tiled_apply_{side}"]


# ``glm_click_fit``'s cold band (PERF.md §4): 1,024 row blocks x 123 column
# blocks of unit codes, 8 deep both ways since the depths are chosen by
# cost (16 forward and 24 backward without a spill), and the sweep's other
# depths: the bodies a basic block and the window pick lower under Mosaic
# within the kernel's VMEM.
@pytest.mark.parametrize("side, a", [
    ("fwd", 16), ("bwd", 24), ("fwd", 8), ("fwd", 32), ("bwd", 40),
    ("bwd", 8),
])
def test_cold_kernel_compiles_at_cell_depths(monkeypatch, one_chip, side, a):
    from photon_ml_tpu.ops.sparse_pallas import COLD_TILE, _cold_apply

    monkeypatch.delenv("PHOTON_PALLAS_INTERPRET", raising=False)
    nbo, nbg = (1024, 123) if side == "fwd" else (123, 1024)

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with jax.enable_x64(False):
        text = _cold_apply.lower(
            struct((nbo, nbg, a, WIN), jnp.int32), struct((1,), jnp.float32),
            struct((nbg * COLD_TILE,), jnp.float32), nbo=nbo, nbg=nbg,
            square=False, side=side, unit=True).compile().as_text()
    (call,) = _pallas_calls(text)
    assert call.rpartition(".")[0] == f"_cold_apply_{side}"


# The grids of the two benchmark cells (PERF.md §4), shapes only: row blocks
# x column blocks, the two depths, valued or unit.  Mosaic's verdict on the
# kernel at these depths (the accumulator's VMEM beside the step's blocks,
# the sublane-group reshape, 6 to 16 tile bodies a block) is then known
# before a chip run.  The third grid is no cell's: the one where
# ``_pick_rect`` batches the most output blocks into a step (139, with their
# accumulators), the most VMEM a step ever holds beside its input blocks.
CELL_GRIDS = {
    "glm_lbfgs_fit": dict(nbr=393, nbc=24, a_f=128, a_b=160, unit=False),
    "game_cd_fit": dict(nbr=9766, nbc=14, a_f=32, a_b=48, unit=True),
    "shallow_one_gather_block": dict(
        nbr=8 * 139, nbc=1, a_f=16, a_b=16, unit=True),
}


@pytest.mark.parametrize("cell, side, square", [
    ("glm_lbfgs_fit", "fwd", False), ("glm_lbfgs_fit", "bwd", False),
    ("glm_lbfgs_fit", "fwd", True), ("glm_lbfgs_fit", "bwd", True),
    ("game_cd_fit", "fwd", False), ("game_cd_fit", "bwd", False),
    ("shallow_one_gather_block", "fwd", False),
])
def test_kernel_compiles_at_cell_depths(monkeypatch, one_chip, cell, side,
                                        square):
    monkeypatch.delenv("PHOTON_PALLAS_INTERPRET", raising=False)
    g = CELL_GRIDS[cell]
    nbo, nbg, a = ((g["nbr"], g["nbc"], g["a_f"]) if side == "fwd"
                   else (g["nbc"], g["nbr"], g["a_b"]))

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    code = struct((nbo, nbg, a, WIN), CODE_DTYPE)
    val = (struct((1,), jnp.float32) if g["unit"]
           else struct((nbo, nbg, a, WIN), jnp.float32))
    vec = struct((nbg * TILE_C,), jnp.float32)
    with jax.enable_x64(False):
        text = _tiled_apply.lower(
            code, val, vec, nbo=nbo, nbg=nbg, square=square, side=side,
            unit=g["unit"]).compile().as_text()
    (call,) = _kernel_calls(text)
    assert call.rpartition(".")[0] == f"_tiled_apply_{side}"


# The dense stripes' share of a product at the benchmark cell's width: 300
# stripes over 804,414 rows (0.97 GB).  Each form has to stay one
# bandwidth-bound fusion over the block: no MXU convolution (whose default
# precision would round the operands to bfloat16) and no temporary of the
# block's size (the squared forms square inside the fusion).
STRIPES, LONG_AXIS = 300, 804_414


@pytest.mark.parametrize("form, squared", [
    ("_stripes_t_dot", False), ("_stripes_dot", False),
    ("_stripes_t_dot", True), ("_stripes_dot", True),
])
def test_stripe_products_are_fusions(one_chip, form, squared):
    def struct(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    block = struct(STRIPES, LONG_AXIS)
    dot = getattr(PallasSparseMatrix, form)
    sq = (lambda x: x * x) if squared else (lambda x: x)
    if form == "_stripes_t_dot":
        fn, vec = (lambda d, v: dot(v, sq(d))), struct(STRIPES)
    else:
        fn, vec = (lambda d, v: dot(sq(d), v)), struct(LONG_AXIS)
    with jax.enable_x64(False):
        compiled = jax.jit(fn).lower(block, vec).compile()
    text = compiled.as_text()
    assert " fusion(" in text
    assert " convolution(" not in text and "bf16[" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < (
        STRIPES * LONG_AXIS * 4 // 8)


# The Newton body of a random effect's block at the short-row shapes of the
# two GAME cells' ladders (lanes, rows, columns; stored rows-minor as the
# chip stores them).  There the Hessian is built as elementwise pairs, whose
# product is (E, R, D, D) before its reduction: 4.3 GB at 38,069 x 64 x 21.
# The compiler has to fuse the two, so the program holds no temporary of
# anything like that size, and no matrix-unit product at all; one bucket up
# (128 rows of 21 columns) the Hessian is the one batched matmul.
@pytest.mark.parametrize("lanes, rows, dim, by_pairs", [
    (10_548, 32, 21, True), (38_069, 64, 21, True), (6_266, 256, 9, True),
    (34_126, 128, 21, False),
])
def test_newton_body_holds_no_pairs_product(one_chip, lanes, rows, dim,
                                            by_pairs):
    from photon_ml_tpu.game.coordinates import _make_block_solver
    from photon_ml_tpu.game.data import EntityBlock
    from photon_ml_tpu.optim.problem import (
        GlmOptimizationConfig,
        OptimizerConfig,
    )
    from photon_ml_tpu.optim.regularization import RegularizationContext

    def struct(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    block = EntityBlock(
        X=struct(lanes, dim, rows), labels=struct(lanes, rows),
        weights=struct(lanes, rows),
        col_map=struct(lanes, dim, dtype=jnp.int32),
        row_index=struct(lanes, rows, dtype=jnp.int32),
        n_entities=lanes, rows_per_entity=rows, block_dim=dim, x_minor="r")
    solver = _make_block_solver("logistic", GlmOptimizationConfig(
        optimizer=OptimizerConfig(max_iters=30, tolerance=1e-7),
        regularization=RegularizationContext.l2()))
    assert solver.path(block) == "newton_direct"
    with jax.enable_x64(False):
        compiled = solver.counted.lower(
            block, struct(lanes, rows), struct(lanes, dim), struct(),
            struct()).compile()
    products = compiled.as_text().count(" convolution(")
    if by_pairs:
        assert products == 0
        assert compiled.memory_analysis().temp_size_in_bytes < (
            lanes * rows * dim * dim * 4 // 16)
    else:
        assert products == 1


# The whole trust-region Newton solve of ``glm_tron_fit`` (PERF.md §4): the
# program ``solve_single_device`` jits, at the cell's layout -- the text
# cell's grid and depths, 360 column stripes over 804,414 rows, a column
# permutation -- shapes only.  Two nested ``while`` loops around the two
# kernel orientations: the chip's compiler has to take it whole (VMEM beside
# the loops' carries, the device's memory), and the Steihaug CG's body, the
# inner loop, has to hold one forward and one backward product and nothing
# of the value+gradient's.  It also shows what the source does not: the
# forward product that ``tron_solve`` writes twice per trial point is in the
# program once.
TRON_ROWS, TRON_COLS, TRON_STRIPES = 804_414, 47_237, 360


def _while_bodies(hlo_text):
    """{computation name: its text} for every ``while`` body of a module."""
    import re

    bodies = set(re.findall(r"\bwhile\(.*?body=%?([\w.\-]+)", hlo_text))
    blocks, name = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"^%?([\w.\-]+) \(.*\) -> .*\{$", line)
        if head:
            name = head.group(1)
        if name in bodies:
            blocks[name] = blocks.get(name, "") + line + "\n"
    return blocks


def test_tron_solve_compiles_at_the_cell_layout(monkeypatch, one_chip):
    import dataclasses

    from photon_ml_tpu.data.dataset import GlmData
    from photon_ml_tpu.optim.problem import (
        GlmOptimizationConfig,
        GlmOptimizationProblem,
        OptimizerConfig,
        OptimizerType,
    )
    from photon_ml_tpu.optim.regularization import RegularizationContext

    monkeypatch.delenv("PHOTON_PALLAS_INTERPRET", raising=False)
    g = CELL_GRIDS["glm_lbfgs_fit"]  # the same corpus, the same layout

    def struct(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    # a small build gives the placeholders (no spill, no row stripes) and
    # the flags; the cell's shapes then take the leaves' places
    rng = np.random.default_rng(0)
    rows = np.concatenate([rng.integers(0, N_ROWS, NNZ), np.arange(N_ROWS)])
    cols = np.concatenate([rng.integers(0, N_COLS - 1, NNZ),
                           np.full(N_ROWS, N_COLS - 1)])  # an intercept
    small = build_pallas_host(rows, cols, np.ones(len(rows), np.float32),
                              N_ROWS, N_COLS, unit_values=False)
    assert small.has_dense_cols and not small.has_dense_rows
    assert not small.spill.has_spill
    shapes = jax.tree.map(lambda x: struct(x.shape, x.dtype), small)
    nbr, nbc = g["nbr"], g["nbc"]
    layout = dataclasses.replace(
        shapes,
        f_code=struct((nbr, nbc, g["a_f"], WIN), CODE_DTYPE),
        f_val=struct((nbr, nbc, g["a_f"], WIN)),
        b_code=struct((nbc, nbr, g["a_b"], WIN), CODE_DTYPE),
        b_val=struct((nbc, nbr, g["a_b"], WIN)),
        dense_cols=struct((TRON_STRIPES, TRON_ROWS)),
        dense_col_ids=struct((TRON_STRIPES,), jnp.int32),
        col_perm_fwd=struct((TRON_COLS,), jnp.int32),
        col_perm_inv=struct((nbc * TILE_C,), jnp.int32),
        n_rows=TRON_ROWS, n_cols=TRON_COLS, nbr=nbr, nbc=nbc,
        a_f=g["a_f"], a_b=g["a_b"], has_col_perm=True)
    data = GlmData(layout, struct((TRON_ROWS,)), struct((TRON_ROWS,)),
                   struct((TRON_ROWS,)))
    problem = GlmOptimizationProblem("logistic", GlmOptimizationConfig(
        optimizer=OptimizerConfig(optimizer=OptimizerType.TRON, max_iters=10,
                                  tolerance=0.005),
        regularization=RegularizationContext.l2()))
    with jax.enable_x64(False):
        compiled = jax.jit(
            lambda d, lam, w0: problem.solve(d, lam, w0)).lower(
                data, struct(()), struct((TRON_COLS,))).compile()
    text = compiled.as_text()
    bodies = _while_bodies(text)
    assert len(bodies) == 2, sorted(bodies)  # the outer loop and the CG
    kernels = {name: sorted(c.rpartition(".")[0] for c in _kernel_calls(b))
               for name, b in bodies.items()}
    # The CG: one Hessian-vector product, a forward and a backward kernel.
    # The outer iteration: the trial point's value+gradient, and no third
    # product: the margins ``d2_weights(w_try)`` asks for again are the
    # value+gradient's own, two identical calls that the compiler merges
    # into one (so is the pair at the solve's start, outside both loops).
    pair = ["_tiled_apply_bwd", "_tiled_apply_fwd"]
    assert list(kernels.values()) == [pair, pair]
    assert sorted(c.rpartition(".")[0] for c in _kernel_calls(text)) == (
        sorted(3 * pair))
    # the matrix and the stripes are arguments; what the program adds to
    # them is row and column vectors, far from a second copy of either
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2 ** 20
