"""The scan program compiled for a v5e that is described, not attached
(`jax.experimental.topologies`): what the TPU compiler makes of the
entry form of the range compares (search/multiblock.py
`multi_entry_mask`, PR 36) at a share16 group's shapes. Nothing runs,
so nothing here is a time.

Held: the kv columns go through the term loop as they are staged, the
entries on the lanes. With a range table under 32 wide beside them the
compiler took them through a two-term loop slot-minor instead: a copy
of both columns for every launch, 3.2 GB of scratch, 17.0 ms a launch
where 2.9 is due (my chip run, PR 36). `_RANGE_BLOCK_MIN` pads the
table; this file says if a compiler or a change undoes that. And the
fused launch given its seven tables as one packed operand (PR 39) is
the program it was given them one by one: the prelude's slices in front
of the term loop move nothing the loop reads. And the one packed output
(PR 41) leaves the scan in front of it op for op what it was when it
returned four arrays: one more small fusion, no other pass. And a
structural launch that joins by ancestor (PR 46) is running maxes over
the span axis at the cell's size: no loop, no lookup a span row. And
the launch that reduces (`?agg=red`, the cell `red16.dashboard`) fits the
chip at the cell's group, solo and at the eight members the coalescer
fuses, with the kv columns read as they are staged.
"""

import math

import jax
import jax.numpy as jnp
import pytest

P, E, C, B = 4096, 1024, 16, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps it from being described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _group(one_chip, val_dtype):
    """(S, cols): a shape on the described chip, and a share16 group's
    seven page arrays."""
    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return S, (S((P, E, C), jnp.int8), S((P, E, C), val_dtype),
               S((P, E), jnp.uint32), S((P, E), jnp.uint32),
               S((P, E), jnp.uint32), S((P, E), jnp.bool_),
               S((P,), jnp.int32))


@pytest.mark.parametrize("Q,T,R,val_dtype", [
    (None, 2, 8, jnp.int32),      # highcard.substring: int32 ids
    (None, 2, 16, jnp.int16),     # share16.triage: the role infix
    (4, 2, 16, jnp.int16),        # and its fused launch
], ids=["solo-2x8-int32", "solo-2x16-int16", "fused-4x2x16-int16"])
def test_entry_form_keeps_the_kv_columns_entry_minor(
        Q, T, R, val_dtype, one_chip, no_compile_cache):
    from tempo_tpu.search.engine import DEFAULT_TOP_K, resolve_top_k
    from tempo_tpu.search.multiblock import ENTRY_RANGES, batch_scan_kernel

    assert R >= ENTRY_RANGES
    S, cols = _group(one_chip, val_dtype)
    q = () if Q is None else (Q,)
    tables = (S((*q, B, T), jnp.int32), S((*q, B, T, R, 2), jnp.int32),
              None if Q is None else S((Q, T), jnp.bool_),
              *[S(q, jnp.uint32)] * 4)
    compiled = batch_scan_kernel.lower(
        *cols, *tables, n_terms=T,
        top_k=resolve_top_k(DEFAULT_TOP_K, 20)).compile()
    text = compiled.as_text()
    assert "copy(%kv_key" not in text and "copy(%kv_val" not in text
    # the fused launch's [Q, P, E, C] key matches are 0.27 GB of it
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("Q,T,R,val_dtype", [
    (2, 2, 1, jnp.int16),         # share16.scan: nearly every fused launch
    (2, 1, 512, jnp.int32),       # highcard.substring: 1 MB of ranges
], ids=["fused-2x2x1-int16", "fused-2x1x512-int32"])
def test_the_packed_operand_leaves_the_fused_program_as_it_was(
        Q, T, R, val_dtype, one_chip, no_compile_cache):
    """One operand where there were seven, taken apart by static
    slices and bit-casts: the same loops, no copy of a kv column, the
    scratch within a hundredth."""
    import re

    from tempo_tpu.search.engine import DEFAULT_TOP_K, resolve_top_k
    from tempo_tpu.search.multiblock import _packed_slots, batch_scan_kernel

    S, cols = _group(one_chip, val_dtype)
    dims = (Q, B, T, R)
    forms = {
        "seven": ((S((Q, B, T), jnp.int32), S((Q, B, T, R, 2), jnp.int32),
                   S((Q, T), jnp.bool_), *[S((Q,), jnp.uint32)] * 4), None),
        "packed": ((S((_packed_slots(dims)[-1][1],), jnp.int32),
                    *[None] * 6), dims)}
    loops, temp = {}, {}
    for form, (tables, packed) in forms.items():
        compiled = batch_scan_kernel.lower(
            *cols, *tables, n_terms=T, packed=packed,
            top_k=resolve_top_k(DEFAULT_TOP_K, 20)).compile()
        text = compiled.as_text()
        assert "copy(%kv_key" not in text and "copy(%kv_val" not in text
        loops[form] = len(re.findall(r" while\(", text))
        temp[form] = compiled.memory_analysis().temp_size_in_bytes
    assert loops["packed"] == loops["seven"] > 0
    assert abs(temp["packed"] - temp["seven"]) < temp["seven"] / 100


def _ops(text):
    """(opcode, shape) of every instruction of a compiled module,
    layouts and names dropped: what the program does, countable."""
    import collections
    import re

    return collections.Counter(
        (m.group(2), re.sub(r"\{[^}]*\}", "", m.group(1)))
        for m in re.finditer(r"= (\S+) (\w[\w\-]*)\(", text))


@pytest.mark.parametrize("Q", [None, 2], ids=["solo", "fused"])
def test_the_packed_output_leaves_the_scan_in_front_of_it_as_it_was(
        Q, one_chip, no_compile_cache):
    """`_scan_pages` jitted alone returns the four arrays the program
    returned until PR 41; `batch_scan_kernel` packs them. Compiled for
    the v5e at a share16.scan launch's shapes, every op of the first is
    in the second, shape for shape, and what the second adds works on
    the packed row alone. (Without `pack_out`'s barrier the compiler
    re-fuses the count and the scores: a pass over a copy of
    entry_start that the parent did not make.)"""
    import functools

    from tempo_tpu.search import multiblock

    T, R, K = 2, 1, 128
    S, cols = _group(one_chip, jnp.int16)
    q = () if Q is None else (Q,)
    tables = (S((*q, B, T), jnp.int32), S((*q, B, T, R, 2), jnp.int32),
              None if Q is None else S((Q, T), jnp.bool_),
              *[S(q, jnp.uint32)] * 4)
    four = jax.jit(functools.partial(
        multiblock._scan_pages, n_terms=T, top_k=K, widths=None, plan=None,
        agg=None)).lower(*cols, *tables, *[None] * 7).compile().as_text()
    one = multiblock.batch_scan_kernel.lower(
        *cols, *tables, n_terms=T, top_k=K).compile().as_text()
    was, now = _ops(four), _ops(one)
    # the parent's ROOT tuple is the one op the packed program lacks
    gone = {op for op in was - now if op[0] != "tuple"}
    assert not gone, gone
    row = (Q or 1) * (2 + 2 * K)
    for (op, shape), n in (now - was).items():
        dims = [int(d) for d in shape.split("[")[-1].rstrip("]").split(",")
                if d]
        assert math.prod(dims) <= row, (op, shape, n)
    assert now[("fusion", f"s32[{'2,' if Q else ''}{2 + 2 * K}]")] == 1


@pytest.mark.parametrize("rel,lookups", [("desc", 0), ("child", 1)])
def test_a_join_by_ancestor_is_no_loop_and_no_lookup_a_span_row(
        rel, lookups, one_chip, no_compile_cache):
    """The `desc` launch of `calltree16.structural`'s group (8 blocks,
    2^23 span rows of 4 kv slots) compiled for the v5e: the join is
    `reduce-window`s as the running sum of `_seg_count` is, the program
    holds no `while` and no gather whose result is as long as the span
    axis, and its scratch is a few passes' worth. `child` beside it
    keeps its one lookup through the parent column."""
    import re

    from tempo_tpu.search import ir, structural
    from tempo_tpu.search.multiblock import batch_scan_kernel

    pages, blocks, spans, slots = 512, 8, 1 << 23, 4

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cols = (S((pages, E, C), jnp.int8), S((pages, E, C), jnp.int16),
            *[S((pages, E), jnp.uint32)] * 3, S((pages, E), jnp.bool_),
            S((pages,), jnp.int32))
    span_cols = {
        **{n: S((spans,), jnp.int32)
           for n in ("span_trace", "span_parent", "span_last")},
        "span_tile_block": S((spans // structural.SPAN_TILE,), jnp.int32),
        "span_dur": S((spans,), jnp.uint32),
        "span_kind": S((spans,), jnp.int8),
        "span_kv_key": S((spans, slots), jnp.int32),
        "span_kv_val": S((spans, slots), jnp.int32),
        "entry_span_begin": S((pages, E), jnp.int32),
        "entry_span_count": S((pages, E), jnp.int32)}
    plan = structural._LeafCollector().lower_trace(ir.parse(
        '{"exists": {"%s": {"%s": {"tag": {"k": "service.name", "v": "a"}},'
        ' "%s": {"tag": {"k": "name", "v": "b"}}}}}'
        % ((rel, "anc", "span") if rel == "desc"
           else (rel, "parent", "child"))))
    tables = (S((blocks, 0), jnp.int32), S((blocks, 0, 1, 2), jnp.int32),
              None, *[S((), jnp.uint32)] * 4)
    s_tables = (S((blocks, 2), jnp.int32), S((blocks, 2, 1, 2), jnp.int32),
                *[None] * 5)
    compiled = batch_scan_kernel.lower(
        *cols, *tables, None, None, None, span_cols, s_tables, None,
        n_terms=0, top_k=128, plan=plan).compile()
    text = compiled.as_text()
    assert not re.findall(r" while\(", text)
    assert len(re.findall(r"= \w+\[%d\]\S* gather\(" % spans, text)) \
        == lookups
    assert ("reduce-window(" in text) and compiled.memory_analysis() \
        .temp_size_in_bytes < 256 << 20


@pytest.mark.parametrize("Q", [None, 8], ids=["solo", "fused-8"])
def test_the_launch_that_reduces_fits_the_chip_at_the_cells_group(
        Q, one_chip, no_compile_cache):
    """`red16.dashboard`'s launch (a full group, 4,096 pages, the key
    column [P, E] int32, K = 7,680) compiled for the v5e: the reduction
    sorts the key column (`sort` ops, the names `sort_share.red` reads
    from the chip's trace), the kv columns are not copied for it, and
    the program's scratch stays what the scan's was: 1.2 GB at the
    eight members a burst fuses, beside the 2.8 GB the cell's columns
    and keys hold (nothing of a sort a member is kept across members)."""
    import re

    from tempo_tpu.search.engine import DEFAULT_TOP_K, resolve_top_k
    from tempo_tpu.search.multiblock import _packed_slots, batch_scan_kernel

    T, R, K = 1, 16, 256 * 15 * 2
    S, cols = _group(one_chip, jnp.int16)
    if Q is None:
        tables, packed = (S((B, T), jnp.int32), S((B, T, R, 2), jnp.int32),
                          None, *[S((), jnp.uint32)] * 4), None
    else:
        packed = (Q, B, T, R)
        tables = (S((_packed_slots(packed)[-1][1],), jnp.int32),
                  *[None] * 6)
    compiled = batch_scan_kernel.lower(
        *cols, *tables, None, None, None, None, None, S((P, E), jnp.int32),
        n_terms=T, packed=packed, agg=K,
        top_k=resolve_top_k(DEFAULT_TOP_K, 20)).compile()
    text = compiled.as_text()
    assert "copy(%kv_key" not in text and "copy(%kv_val" not in text
    assert re.findall(r"%sort[.\d]* = ", text)
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= (Q or 1) * 4 * (K + 2 + 2 * 128)
    assert mem.temp_size_in_bytes < (3 << 29 if Q else 1 << 29)
