"""The chip's compiler, asked in the sandbox: the Pallas kernels of the
default on-chip path must compile for a described (not attached) v5e.

Interpret-mode parity tests cannot see what Mosaic refuses — a missing
uint32<->float32 cast and a dynamic lane slice got through every one of
them (PR 22).  These compile each kernel with ``interpret=False`` at
batch 1024 against ``v5e:2x2`` and look for the ``tpu_custom_call`` in
the result.  Nothing runs, so they say nothing about results or time.

The topology is described inside a module-scoped fixture — never at
import, in a ``skipif`` or in ``parametrize`` arguments: only one
process may load libtpu, every xdist worker imports this file, and only
the worker that runs it may make the call.  Keep these tests in this
ONE file for the same reason.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dkg_tpu.groups import device as gd
from dkg_tpu.ops import pallas_field as pf
from dkg_tpu.ops import pallas_mxu as pm
from dkg_tpu.ops import pallas_point as pp

B = 1024  # batch lanes


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here, or it is locked
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a topology compile is written to the persistent cache but cannot
    # be read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernel(name: str, cs: gd.CurveSpec):
    """(function, operand shapes) for one kernel at the smoke's widths."""
    fs, L, C = cs.field, cs.field.limbs, cs.ncoords
    elem, point = (B, L), (B, C, L)
    table = {
        "mod_mul": (lambda a, b: pf.mod_mul(fs, a, b, interpret=False), [elem] * 2),
        "mod_madd": (lambda a, b, c: pf.mod_madd(fs, a, b, c, interpret=False), [elem] * 3),
        "mxu_mod_mul": (lambda a, b: pm.mxu_mod_mul(fs, a, b, interpret=False), [elem] * 2),
        "mod_pow_const": (
            lambda a: pf.mod_pow_const(fs, a, fs.modulus - 2, interpret=False), [elem],
        ),
        "pt_add": (lambda p, q: pp.pt_add(cs, p, q, interpret=False), [point] * 2),
        "pt_madd": (lambda p, q: pp.pt_madd(cs, p, q, interpret=False), [point] * 2),
        "pt_double": (lambda p: pp.pt_double(cs, p, interpret=False), [point]),
        "pt_window_step": (
            lambda p, q: pp.pt_window_step(cs, p, q, 4, interpret=False), [point] * 2,
        ),
        "pt_ladder_mul_add": (
            lambda p, a, x: pp.pt_ladder_mul_add(cs, p, a, x, 11, interpret=False),
            [point, point, (B,)],
        ),
    }
    fn, shapes = table[name]
    return fn, [(s, jnp.uint32) for s in shapes]


def _compiles_to_a_tpu_kernel(one_chip, curve: str, name: str) -> None:
    fn, operands = _kernel(name, gd.ALL_CURVES[curve])
    specs = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in operands]
    compiled = jax.jit(fn).lower(*specs).compile()  # raises what the chip's compiler raises
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "curve,name",
    [
        ("secp256k1", "mod_mul"),
        ("secp256k1", "mod_madd"),
        ("secp256k1", "mxu_mod_mul"),
        ("secp256k1", "pt_add"),
        ("secp256k1", "pt_madd"),
        ("secp256k1", "pt_double"),
        ("ristretto255", "pt_add"),
        ("ristretto255", "pt_double"),
        # the multi-op Edwards bodies (64-row blocks): the tier every curve runs since PR 42,
        # and the two Mosaic was once seen not to return from (17 s and 11 s here)
        ("ristretto255", "pt_window_step"),
        ("ristretto255", "pt_ladder_mul_add"),
        ("secp256k1", "mod_pow_const"),
        ("ristretto255", "mod_pow_const"),
        # the 24-limb base field (72-row point blocks, 576 partial products a
        # multiply): what `ceremony_bls_n1024.closed` runs on the chip
        ("bls12_381_g1", "mod_mul"),
        ("bls12_381_g1", "mod_madd"),
        ("bls12_381_g1", "mxu_mod_mul"),
        ("bls12_381_g1", "mod_pow_const"),
        ("bls12_381_g1", "pt_add"),
        ("bls12_381_g1", "pt_madd"),
        ("bls12_381_g1", "pt_double"),
    ],
)
def test_kernel_compiles_for_v5e(one_chip, curve, name, monkeypatch):
    monkeypatch.delenv("DKG_TPU_MUL", raising=False)  # the default (mxu) multiply core
    assert pf.rows_mul_dispatch(gd.ALL_CURVES[curve].field, False) == "mxu"
    _compiles_to_a_tpu_kernel(one_chip, curve, name)


@pytest.mark.slow  # 20-30 s each in the sandbox at 16 limbs
@pytest.mark.parametrize("curve", ["secp256k1", "bls12_381_g1"])
@pytest.mark.parametrize("name", ["pt_window_step", "pt_ladder_mul_add"])
def test_multi_op_kernel_compiles_for_v5e(one_chip, name, curve, monkeypatch):
    monkeypatch.delenv("DKG_TPU_MUL", raising=False)
    _compiles_to_a_tpu_kernel(one_chip, curve, name)


@pytest.mark.parametrize("shape", [(128, 6, 3, 16), (8, 3, 16), (8, 16, 6, 3, 16)], ids=str)
def test_affine_canon_lowers_to_the_kernel_for_v5e(one_chip, shape, monkeypatch):
    """The digest leg's canonicalisation at a width-8 (16,5) convoy's
    shapes (the commitment tensors flat, the master keys, and the
    commitment tensors with their ceremony axis, as the served leg hands
    them over since PR 43: flattened under the trace, so the first
    shape's program), as the chip traces it: one module, the inversion
    one Mosaic launch (one row, so no Montgomery scan, and the window
    chain loops inside the kernel), and no XLA ``while`` but the carry
    ripples of the closing X/Z, Y/Z multiply.  The trace-time dispatch is steered here, in the test:
    this process's backend is the CPU.  The steered path is part of the
    jitted program's key, so this trace and the eager CPU ones of the
    parity tests never answer for each other, in either order."""
    from dkg_tpu.fields import device as fd

    monkeypatch.setenv("DKG_TPU_ASSUME_BACKEND", "tpu")
    monkeypatch.delenv("DKG_TPU_PALLAS", raising=False)
    monkeypatch.delenv("DKG_TPU_MUL", raising=False)
    cs = gd.ALL_CURVES["secp256k1"]
    spec = jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=one_chip)
    assert gd._canon_path() == "fused"
    text = gd._affine_canon_jit.lower(cs, "fused", spec).compile().as_text()
    assert "jit_affine_canon" in text
    assert text.count("tpu_custom_call") == 1

    xy = jax.ShapeDtypeStruct(shape[:-2] + (2, 16), jnp.uint32, sharding=one_chip)
    zi = jax.ShapeDtypeStruct(shape[:-2] + (1, 16), jnp.uint32, sharding=one_chip)
    closing = jax.jit(lambda a, b: fd.mul(cs.field, a, b)).lower(xy, zi).compile().as_text()
    assert text.count(" while(") == closing.count(" while(") > 0


@pytest.mark.parametrize("lead", [(8, 16), (16,)], ids=["convoy", "width1"])
def test_the_hash_tree_takes_the_round1_tensors_as_they_are_for_v5e(one_chip, lead):
    """The three tree programs of a (16,5) convoy's digest leg, handed
    deal's outputs in their own shapes (a commitment tensor; the share and
    hiding matrices as a pair): the flattening to rows, the cast and the
    joining compile inside the one module the device trace knows, a level
    one Mosaic kernel (``interpret=False``: the chip's compiler)."""
    from dkg_tpu.crypto import device_hash as dh

    def spec(*tail):
        return jax.ShapeDtypeStruct(lead + tail, jnp.uint32, sharding=one_chip)

    for parts, leaves in (((spec(6, 3, 16),), 32), ((spec(16, 16), spec(16, 16)), 32)):
        compiled = dh._tree_from_words_jit.lower(parts, np.uint32(3), len(lead), False).compile()
        text = compiled.as_text()
        assert "jit__tree_from_words_jit" in text
        assert _tree_kernels(text) == leaves.bit_length() + 1  # the leaves, log2(leaves) levels, the root
        (out,) = jax.tree_util.tree_leaves(compiled.out_info)
        assert out.shape == (int(np.prod(lead)), 8)


def _tree_kernels(text: str) -> int:
    return len(re.findall(r"custom-call\(.*custom_call_target=\"tpu_custom_call\"", text))


@pytest.mark.parametrize(
    "shapes, leaves",
    [
        (((1024, 342, 2, 16),), 1024),  # secp256k1 n=1024: a dealer's commitments, 10,944 words
        (((1024, 1024, 16), (1024, 1024, 16)), 2048),  # its share and hiding rows, 32,768 words
        (((1024, 342, 2, 24),), 2048),  # BLS12-381: 16,416 words
        (((256, 4096, 16), (256, 4096, 16)), 8192),  # the mesh's digest chunk of a (4096,1365) request
        (((64, 64, 16), (64, 64, 16)), 128),  # rows 64: a width-1 (64,16) convoy
        (((320, 64, 16), (320, 64, 16)), 128),  # rows 320: a block and a cut one
    ],
    ids=["a_n1024", "sr_n1024", "a_bls_n1024", "sr_mesh_chunk", "sr_rows64", "sr_rows320"],
)
def test_the_hash_tree_compiles_at_the_cells_shapes_for_v5e(one_chip, shapes, leaves):
    """What Mosaic refuses no interpret-mode test sees, and rho is on no
    outcome: every level of the tree at the shapes the benchmark's cells
    send is a kernel the chip's compiler takes, and the program keeps no
    copy of the padded words beside the word-major one."""
    from dkg_tpu.crypto import device_hash as dh

    parts = tuple(jax.ShapeDtypeStruct(s, jnp.uint32, sharding=one_chip) for s in shapes)
    compiled = dh._tree_from_words_jit.lower(parts, np.uint32(3), 1, False).compile()
    assert _tree_kernels(compiled.as_text()) == leaves.bit_length() + 1
    words = shapes[0][0] * leaves * 16 * 4  # the padded leaves of every row, in bytes
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5 * words


def _layout_changes(text: str, min_elems: int) -> list[tuple[str, str]]:
    """(computation, instruction) of every ``transpose`` that permutes,
    and of every ``copy`` (or ``transpose`` in place) whose operand has
    another minor-to-major order, of a u32 array of at least
    ``min_elems`` elements in a compiled module's text."""
    layouts = {m[1]: m[2] for m in re.finditer(r"%([\w.\-]+) = u32\[[\d,]*\](\{[\d,]*)", text)}
    found, comp = [], ""
    for line in text.split("\n"):
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$", line)
        if head:
            comp = head[1]
            continue
        m = re.match(
            r"\s*(?:ROOT )?%[\w.\-]+ = u32\[([\d,]*)\](\{[\d,]*)\S* (copy|transpose)\(%([\w.\-]+)\)(?:, dimensions=\{([\d,]*)\})?",
            line,
        )
        if m and np.prod([int(d) for d in m[1].split(",") if d]) >= min_elems:
            permutes = m[3] == "transpose" and m[5] != ",".join(map(str, range(m[5].count(",") + 1)))
            if permutes or layouts.get(m[4]) != m[2]:
                found.append((comp, line.strip()[:120]))
    return found


def _called_from(text: str, root: str) -> set[str]:
    """``root`` and every computation it reaches (fusions, nested loops)."""
    calls, comp = {}, ""
    for line in text.split("\n"):
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$", line)
        if head:
            comp = head[1]
        else:
            calls.setdefault(comp, set()).update(
                re.findall(r"(?:calls|body|condition|to_apply)=%([\w.\-]+)", line)
            )
    seen, todo = set(), [root]
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            todo.extend(calls.get(c, ()))
    return seen


@pytest.mark.parametrize("curve", ["ristretto255", "secp256k1", "bls12_381_g1"])
def test_point_rlc_block_form_keeps_its_layout_for_v5e(one_chip, curve, monkeypatch):
    """The point-RLC as the chip traces it (fused kernels on, Straus) at
    8 dealers x 32 columns, two lane blocks: no ``gather``, and a point
    tensor changes layout at the ends alone: the argument's relayout
    and the one transposition to lane blocks in the entry computation
    (the way out is a fusion), nothing inside a loop.  The parent of PR
    31 is the counter-example: the same shape compiled to 5 ``gather``
    and 16 such copies and transposes, 13 of them inside the window
    loop (the table's concatenate, the ``take_along_axis``, and two
    conversions a tree level), and that without the ``copy_bitcast``
    fusions this count does not see.  Every curve takes the fused
    window kernel (64-row blocks on ristretto255, 72-row at 24 limbs)."""
    from dkg_tpu.dkg import ceremony as ce

    monkeypatch.setenv("DKG_TPU_ASSUME_BACKEND", "tpu")
    for name in ("DKG_TPU_PALLAS", "DKG_TPU_MUL", "DKG_TPU_RLC", "DKG_TPU_RLC_CHUNK"):
        monkeypatch.delenv(name, raising=False)
    cs = gd.ALL_CURVES[curve]
    m, cols, nbits = 8, 32, 8
    w = jax.ShapeDtypeStruct((m, cs.scalar.limbs), jnp.uint32, sharding=one_chip)
    p = jax.ShapeDtypeStruct((m, cols, cs.ncoords, cs.field.limbs), jnp.uint32, sharding=one_chip)
    text = jax.jit(lambda w_, p_: ce._point_rlc(cs, w_, p_, nbits)).lower(w, p).compile().as_text()
    assert "tpu_custom_call" in text and " while(" in text
    assert " gather(" not in text
    changes = _layout_changes(text, cs.ncoords * cs.field.limbs * pp.BLOCK)
    assert len(changes) <= 2, changes
    assert all(comp.startswith("main") for comp, _ in changes), changes


@pytest.mark.parametrize("curve", ["ristretto255", "secp256k1", "bls12_381_g1"])
def test_fixed_base_block_form_keeps_its_layout_for_v5e(one_chip, curve, monkeypatch):
    """The fixed-base multiply as the chip traces it (fused kernels on,
    the 16-bit table of the on-chip default) at 8 x 40 lanes, three lane
    blocks: the window loop carries the accumulator as (nb, C·L, BLOCK)
    blocks and nothing in the loop, its fusions included, changes the
    layout of the accumulator.  What does change layout in a step is
    the gathered entry, once (rows -> blocks), and the window's slice
    of the table (the device keeps a ``(NW, 65536, C, L)`` argument with
    the entries minor; a row gather wants the rows minor), once.  The
    parent of PR 35 is the counter-example: the same shape carried
    ``u32[8,40,3,16]`` in tensor form and changed a point-sized array's
    layout four times a step (the gathered entry out of the ``(C, L)``
    minor form, tile-padded to (4, 128), that the gather wrote it in;
    the accumulator and the entry to blocks; the result back) and the
    table's slice once, into that padded form, on all three curves; at
    (1024, 342) the entry's Z left the padded form by a copy of its
    own, a fifth."""
    monkeypatch.setenv("DKG_TPU_ASSUME_BACKEND", "tpu")
    for name in ("DKG_TPU_PALLAS", "DKG_TPU_MUL"):
        monkeypatch.delenv(name, raising=False)
    cs = gd.ALL_CURVES[curve]
    rows = cs.ncoords * cs.field.limbs
    k = jax.ShapeDtypeStruct((8, 40, cs.scalar.limbs), jnp.uint32, sharding=one_chip)
    table = jax.ShapeDtypeStruct(
        (cs.scalar.limbs, 1 << 16, cs.ncoords, cs.field.limbs), jnp.uint32, sharding=one_chip
    )
    text = jax.jit(lambda t_, k_: gd.fixed_base_mul(cs, t_, k_)).lower(table, k).compile().as_text()
    assert "tpu_custom_call" in text
    loops = re.findall(r"= \((.*)\) while\(.*body=%([\w.\-]+)", text)
    assert len(loops) == 1, loops
    carry, body = loops[0]
    assert f"u32[3,{rows},{pp.BLOCK}]{{2,1,0" in carry, carry
    inside = _called_from(text, body)
    changes = [c for c in _layout_changes(text, rows * pp.BLOCK) if c[0] in inside]
    of_the_table = [c for c in changes if "65536" in c[1]]
    assert len(of_the_table) <= 1 and len(changes) - len(of_the_table) <= 1, changes


def test_verify_stack_packs_the_convoys_lanes_for_v5e(one_chip, monkeypatch):
    """A width-8 (16,5) convoy's verify as the chip compiles it: the
    kernels' launches are on the convoy's JOINT lane blocks.  8 x 16 x 6
    = 768 commitment lanes are 6 blocks (the table's ``pt_add``), the
    tree over the dealers halves them 3, 2, 1, 1, and the window step,
    the Horner ladder (8 x 16 = 128 recipients), the fixed-base
    ``pt_madd`` and the closing add are one block each.  The parent of
    PR 39 is the counter-example: a ``vmap`` over the ceremonies, all
    ten launches on 8 blocks, one padded block a ceremony (96 and 6
    live lanes of 128).  The program keeps the name its metric reads."""
    from dkg_tpu.dkg import ceremony as ce
    from dkg_tpu.service import engine

    monkeypatch.setenv("DKG_TPU_ASSUME_BACKEND", "tpu")
    for name in ("DKG_TPU_PALLAS", "DKG_TPU_MUL", "DKG_TPU_RLC", "DKG_TPU_RLC_CHUNK"):
        monkeypatch.delenv(name, raising=False)
    cfg = ce.CeremonyConfig("secp256k1", 16, 5)
    cs, k = cfg.cs, 8
    L, C, S = cs.field.limbs, cs.ncoords, cs.scalar.limbs
    spec = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=one_chip)
    table = spec(S, 1 << 16, C, L)
    try:
        text = engine._verify_stack.lower(
            cfg, spec(k, 16, 6, C, L), spec(k, 16, 16, S), spec(k, 16, 16, S), spec(k, 16, S), 128, table, table
        ).compile().as_text()
    finally:
        # the jitted helpers inside (``eval_point_poly``) were traced as
        # for the chip: they must not answer a CPU caller of these shapes
        jax.clear_caches()
    assert "HloModule jit__verify_stack" in text
    launches = [
        [int(d) for d in shape.split(",")]
        for shape in re.findall(r'u32\[([\d,]*)\]\S* custom-call\([^\n]*custom_call_target="tpu_custom_call"', text)
    ]
    assert all(shape[-2:] == [C * L, pp.BLOCK] for shape in launches), launches
    assert sorted(int(np.prod(shape[:-2])) for shape in launches) == [1] * 7 + [2, 3, 6], launches
