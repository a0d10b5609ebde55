"""The serving scorer's programs with excluded ids compiled at the
e-commerce cell's size for a DESCRIBED v5e chip (no chip attached; the TPU's
compiler is installed here): what the interpreter cannot show, a kernel or
a gather the chip's compiler refuses and a rung that does not fit the
device's memory, costs no chip time; and that the shapes by which
`exclude_device_ms.unseen` finds the exclusions' operations in a trace are
those of the operations under the exclusions' named scopes, at every rung the
cell's batches take.  Since PR 42 also the category form (a row's allowed
items as bits, tested inside the scan kernel and on the chosen blocks) at the
category cell's size beside its resident index, the pattern of
`allow_device_ms.cats` held to ITS scopes, and the programs of a model without
an index held to the parent commit's, lowered for the chip.  Since PR 43
the ALS solve kernel (`ops/solve.py`) at widths on both sides of its rule
for width classes.  The sharded scorer over a 48.19 M-item table on a
described 2x2: each chip's scan of its own shard with the all-gather of
the candidates its one collective, and the chunked parity build and coded
scan fitting a chip beside shard and parity.
Nothing runs; a compile that passes is not a chip run.  One file, the topology described inside a fixture
(`on-chip-measurement`, section 2)."""

import hashlib
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from predictionio_tpu.ops import topk

M, R, K = 9_350_000, 128, 16
# `exclude_device_ms.unseen` finds the exclusions' operations in a trace by
# the SHAPES in their HLO text; the trace names an operation by that text
PATTERN = re.compile(json.loads(
    (Path(__file__).resolve().parents[1]
     / "perfbench/metrics/exclude_device_ms.unseen.json").read_text()
)["args"]["pattern"])
NO_EVENT = ("parameter(", "get-tuple-element(", "bitcast(", "constant(",
            "tuple(")


def operations_by_scope(text: str):
    """`(scope, instruction)` of the instructions a trace would show as
    events: those outside fused computations that do any work, each with
    the innermost `topk.*` named scope of its `op_name`."""
    fused = set(re.findall(r"calls=(%[\w.\-]+)", text))
    inside = None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?(%[\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            inside = head.group(1)
        elif line.startswith("}"):
            inside = None
        elif inside is not None and inside not in fused:
            ins = line.strip().removeprefix("ROOT ")
            body = ins.split(", metadata=")[0]
            if ins.startswith("%") and not any(k in body for k in NO_EVENT):
                name = re.search(r'op_name="([^"]*)"', ins)
                scopes = re.findall(r"topk\.[a-z_]+", name.group(1)) \
                    if name else []
                yield (scopes[-1] if scopes else "", ins)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def as_on_the_chip(monkeypatch):
    """The branches the chip takes: the scan kernel compiled (not
    interpreted) and bfloat16 operands; and no persistent cache, which
    could not read such a program back without a chip."""
    monkeypatch.setattr(topk, "_mxu_operands", lambda: True)
    monkeypatch.setattr(topk, "pallas_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("batch,width", [
    (64, topk.EXCLUDE_LADDER[-1]), (16, 512), (1, 128), (64, 32),
    (64, 128), (1, 512), (64, 2048), (16, topk.EXCLUDE_LADDER[-1])])
def test_a_rung_compiles_for_the_chip_and_fits_it(one_chip, as_on_the_chip,
                                                  batch, width):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    scorer = topk.batch_topk_scores_t.__wrapped__.__wrapped__
    compiled = jax.jit(scorer, static_argnames=("k",)).lower(
        sds((batch, R), jnp.float32),
        topk.ItemTables(None, sds((M, R), jnp.float32)), k=K, mask=None,
        exclude=sds((batch, width), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "the scan is the Pallas kernel"
    assert text.count('custom_call_target="TopK"') == 1
    memory = compiled.memory_analysis()
    # beside the 4.79 GB table: the widest rung's gathered lines and the
    # block maxima, 1.1-1.2 GB; a [B, M] matrix would be 2.4 GB
    assert memory.temp_size_in_bytes < 1.5e9
    assert memory.argument_size_in_bytes < 4.9e9
    if width == topk.EXCLUDE_LADDER[0]:
        return      # the pairwise form: no batch of the e-commerce cell
    # the listed form, every rung the cell's batches take: what the metric
    # reads as the exclusions' own lies under their named scopes (and the
    # lines' maxima, by which this form alone chooses its blocks), never
    # in the scan, the chosen blocks' scores or the k passes; and it holds
    # the gathered lines of the listed ids' blocks, the widest of it
    read = [(scope, ins) for scope, ins in operations_by_scope(text)
            if PATTERN.search(ins)]
    assert {scope for scope, _ in read} <= {
        "topk.exclude_bits", "topk.exclude_blocks", "topk.exclude",
        "topk.blocks"}, read
    for scope, ins in read:
        if scope == "topk.blocks":
            assert re.match(r"%[\w.\-]+ = f32\[\d+,(4568|9136)\]", ins), ins
    lines = [ins for scope, ins in operations_by_scope(text)
             if scope == "topk.exclude_blocks" and "(%table_t_packed" in ins]
    assert lines and all(PATTERN.search(ins) for ins in lines), lines


# -- PR 42: the category form ---------------------------------------------------

CATEGORIES = 4096
ALLOW_PATTERN = re.compile(json.loads(
    (Path(__file__).resolve().parents[1]
     / "perfbench/metrics/allow_device_ms.cats.json").read_text()
)["args"]["pattern"])


def _category_form(one_chip, batch, width):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    scorer = topk.batch_topk_scores_t.__wrapped__.__wrapped__
    return jax.jit(scorer, static_argnames=("k",)).lower(
        sds((batch, R), jnp.float32),
        topk.ItemTables(None, sds((M, R), jnp.float32)), k=K, mask=None,
        exclude=sds((batch, width), jnp.int32) if width else None,
        allow=topk.Allowed(
            sds((batch, topk.CATEGORY_SLOTS), jnp.int32),
            sds((CATEGORIES + 2, topk.allow_words(M) // 1024, 8, 128),
                jnp.uint32)))


@pytest.mark.parametrize("batch", [1, 8, 64])
@pytest.mark.parametrize("width", [0, topk.EXCLUDE_LADDER[0]])
def test_the_category_form_compiles_for_the_chip_and_fits_it(
        one_chip, as_on_the_chip, batch, width):
    compiled = _category_form(one_chip, batch, width).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "the scan is the Pallas kernel"
    assert text.count('custom_call_target="TopK"') == 1
    memory = compiled.memory_analysis()
    # the 4.79 GB table and the 4.80 GB of bit rows are arguments; beside
    # them the batch's words (75 MB at 64 rows), its block maxima and the
    # chosen blocks' gathered rows: no copy of the resident rows (a
    # gather of `[B, C]` rows from them made one: 4.3 GB of temporaries)
    assert memory.temp_size_in_bytes < 0.3e9
    assert 9.5e9 < memory.argument_size_in_bytes < 9.7e9
    assert (memory.temp_size_in_bytes + memory.argument_size_in_bytes
            + memory.output_size_in_bytes) < 15.75e9, "one v5e chip"
    # what `allow_device_ms.cats` reads lies under the allowed bits' own
    # named scopes, never in the scan, the chosen blocks' scores, the
    # excluded ids' drop or the k passes; and it holds each row's read of
    # the resident rows and the re-lay of the batch's words
    read = [(scope, ins) for scope, ins in operations_by_scope(text)
            if ALLOW_PATTERN.search(ins)]
    assert {scope for scope, _ in read} <= {"topk.allow_bits",
                                            "topk.allow"}, read
    words = topk.allow_words(M)
    rows = 8 * -(-batch // 8)
    assert any(f"u32[1,{words // 1024},8,128]" in ins.split(" fusion(")[0]
               for _, ins in read), "a row's read of the resident rows"
    assert any(ins.split(" = ")[1].startswith(f"u32[{rows},{words}]")
               for _, ins in read), "the words re-laid, rows on the sublanes"
    whiles = [ins for _, ins in operations_by_scope(text) if " while(" in ins]
    assert whiles and not any(ALLOW_PATTERN.search(ins) for ins in whiles), \
        "the row loop itself is not read a second time beside its body"


# the parent commit's programs (97c4c5b), lowered for the described chip with
# the Mosaic body's payload (which holds source positions) and the locations
# taken out: what an engine whose model holds no index dispatches
PARENT_PROGRAMS = {
    (1, 0): "eb72f05cc71fceb6", (1, 32): "c09c940daa280e4c",
    (16, 0): "06bf32043c62902f", (16, 32): "053aaac2a60731fa",
    (64, 0): "25ffa214e596ade3", (64, 32): "9e79ffd84d4adc78",
}


@pytest.mark.parametrize("batch,width", sorted(PARENT_PROGRAMS))
def test_programs_without_an_index_are_the_parents(one_chip, as_on_the_chip,
                                                   batch, width):
    """`sim-amazon14-r128`, `ecomm-amazon14-r128` and `rec-yambda-r64` run
    what they ran: without `allow` the lowered program is the parent's,
    text for text, the Mosaic body's source positions aside."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    scorer = topk.batch_topk_scores_t.__wrapped__.__wrapped__
    text = jax.jit(scorer, static_argnames=("k",)).lower(
        sds((batch, R), jnp.float32),
        topk.ItemTables(None, sds((M, R), jnp.float32)), k=K, mask=None,
        exclude=sds((batch, width), jnp.int32) if width else None).as_text()
    assert len(re.findall(r'backend_config = "', text)) == 1
    text = re.sub(r'backend_config = "(?:[^"\\]|\\.)*"',
                  'backend_config = ""', text)
    text = re.sub(r"loc\([^)]*\)", "", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARENT_PROGRAMS[batch, width]


# -- PR 43: the ALS solve kernel in its width classes ---------------------------


@pytest.mark.parametrize("batch,width,bodies", [
    (4096, 128, 4),      # the block sweep's systems: four width classes
    (32768, 64, 1),      # the netflix cell's: the one full-width body
    (1000, 8, 1),        # the four-chip cell's K x K: one block-row
])
def test_the_solve_kernel_compiles_for_the_chip_in_its_width_classes(
        one_chip, as_on_the_chip, batch, width, bodies):
    from predictionio_tpu.ops import solve

    starts = solve._slab_classes(width)
    assert len(starts) == bodies
    text = solve._solve.lower(
        jax.ShapeDtypeStruct((batch, width, width), jnp.float32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((batch, width), jnp.float32, sharding=one_chip),
        tb=solve._tile_rows(width), starts=starts, interpret=False,
    ).compile().as_text()
    assert text.count("tpu_custom_call") >= 1, "the solve is the kernel"


# -- the sharded scorer over a table no chip holds, on a described 2x2 --------

N_ITEMS_X4 = 48_190_000        # rec-amazon23-r128-x4: 12,047,500 rows a chip
CHIP_BYTES = 15.75e9


@pytest.fixture(scope="module")
def four_chips():
    from jax.experimental import topologies
    from jax.sharding import Mesh

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    import numpy as np

    return Mesh(np.array(topo.devices), ("data",))


def _x4(mesh, shape, dtype, spec):
    from jax.sharding import NamedSharding

    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


@pytest.mark.parametrize("batch", [1, 64])
def test_the_sharded_scan_compiles_for_four_chips_and_moves_no_shard(
        four_chips, as_on_the_chip, batch):
    """Each chip's program is the one-chip scan kernel over its 6.17 GB
    shard, and its only collective the all-gather of the [B, k]
    candidates: no collective-permute, no all-reduce, no shard-sized
    temporary."""
    from jax.sharding import PartitionSpec as P

    from predictionio_tpu.ops import distributed_topk

    compiled = distributed_topk._sharded_callable(
        four_chips, "data", K, False).lower(
        _x4(four_chips, (batch, R), jnp.float32, P()),
        _x4(four_chips, (N_ITEMS_X4, R), jnp.float32, P("data", None)),
        n_valid=N_ITEMS_X4).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "the scan is the Pallas kernel"
    collectives = re.findall(
        r" (all-gather|all-reduce|reduce-scatter|all-to-all|"
        r"collective-permute)(?:-start)?\(", text)
    assert set(collectives) == {"all-gather"}, collectives
    gathered = re.findall(r"= (s32\[[\d,]+\])\S* all-gather", text)
    assert gathered == [f"s32[2,{batch},{4 * K}]"], gathered
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes < 6.2e9, "its own shard alone"
    assert memory.temp_size_in_bytes < 0.2e9


def test_parity_and_the_coded_scan_fit_a_chip_beside_the_shard(
        four_chips, as_on_the_chip):
    """The parity built and a late shard rebuilt a row chunk at a time:
    beside the shard and the parity (12.34 GB) under 0.5 GB of
    temporaries, where one whole-shard sum would add 6.17 GB."""
    from jax.sharding import PartitionSpec as P

    from predictionio_tpu.ops import distributed_topk
    from predictionio_tpu.parallel.coded import build_parity_fn

    table = _x4(four_chips, (N_ITEMS_X4, R), jnp.float32, P("data", None))
    parity = build_parity_fn(four_chips).lower(table).compile()
    memory = parity.memory_analysis()
    assert memory.temp_size_in_bytes < 0.5e9
    assert (memory.argument_size_in_bytes + memory.output_size_in_bytes
            + memory.temp_size_in_bytes) < CHIP_BYTES
    coded = distributed_topk._sharded_callable(
        four_chips, "data", K, True).lower(
        _x4(four_chips, (64, R), jnp.float32, P()), table,
        _x4(four_chips, (N_ITEMS_X4 // 4, R), jnp.float32, P()),
        _x4(four_chips, (4,), jnp.float32, P()),
        n_valid=N_ITEMS_X4).compile()
    memory = coded.memory_analysis()
    assert 12.3e9 < memory.argument_size_in_bytes < 12.4e9
    assert memory.temp_size_in_bytes < 0.5e9
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes) < CHIP_BYTES
    assert "collective-permute" not in coded.as_text()
