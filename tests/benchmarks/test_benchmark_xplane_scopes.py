"""lib/xplane_scopes.py, the device's time by PART of the step, on two
traces:

- `data/scopes.toy_hybrid.xplane.pb`, recorded ON THE CHIP by PR 55
  (tools/record_scopes_fixture.py: a toy hybrid of one gated-delta layer
  and one full-attention layer served by a `ContinuousGenerator` on a v5e;
  one chunk tick and the width-1 ticks behind it): the parts the profiler
  kept in the ops' metadata, the tick programs by name, and the sums held
  to lib/xplane_reduce.py's;
- bytes this file encodes itself, by protobuf's wire format, for what the
  recording does not hold: a `ref` stat, a stat of a type the reader does
  not know, an op with no `tf_op`, a `while` whose body's ops are nested in
  it, and a path whose component merely CONTAINS a part's name."""

import os
import struct
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_paths import DATA  # noqa: E402

from lib import xplane_reduce as X  # noqa: E402
from lib import xplane_scopes as S  # noqa: E402

TRACE = os.path.join(DATA, "scopes.toy_hybrid.xplane.pb")


# -- the chip's recording -----------------------------------------------------

@pytest.fixture(scope="module")
def reduced():
    return S.reduce_file(TRACE)


@pytest.fixture(scope="module")
def by_name():
    return X.reduce_file(TRACE)


def test_fixture_is_a_tpu_s_trace_of_a_few_hundred_kilobytes():
    assert os.path.getsize(TRACE) < 1.5e6
    planes = X.read_planes(TRACE)
    assert list(planes) == ["/device:TPU:0"] and planes["/device:TPU:0"]


def test_every_part_of_a_hybrid_s_step_is_found_and_no_other(reduced):
    ran = {part for part, p in reduced["parts"].items() if p["ops"]}
    assert ran == {"embed", "plan", "attn/qkv", "attn/write", "attn/read",
                   "attn/out", "mixer/in", "mixer/chunk", "mixer/step",
                   "mixer/out", "mlp", "head", "sample", S.UNSCOPED}
    # Every part of the vocabulary is a key; what no op ran under reads 0.
    assert set(reduced["parts"]) == {*S.STEP_PARTS, S.UNSCOPED}
    for part in ("moe/route", "moe/experts", "moe/shared", "sample/reveal"):
        assert reduced["parts"][part] == {"self_s": 0.0, "flops": 0.0,
                                          "bytes": 0.0, "ops": 0}
    assert reduced["collisions"] == 0


def test_parts_add_up_to_the_sum_of_self_times_and_busy_is_the_reducer_s(
        reduced, by_name):
    total = sum(p["self_s"] for p in reduced["parts"].values())
    assert total == pytest.approx(sum(by_name["op_seconds"].values()),
                                  abs=1e-9)
    assert reduced["busy_s"] == by_name["busy_s"]
    assert reduced["window_s"] == by_name["window_s"]
    assert reduced["planes"] == by_name["planes"] == 1
    # One op at a time on a TPU's op line: the sum of self times IS busy.
    assert total == pytest.approx(reduced["busy_s"], rel=1e-6)


def test_a_part_holds_its_kernel(reduced, by_name):
    """The Pallas calls keep the names the `kernel.*` readers match, and
    each lies under the part that should hold it."""
    stats = S.read_op_stats(TRACE)["/device:TPU:0"]
    where = {}
    for name, meta in stats.items():
        for kernel in ("gdn_step", "gdn_chunk", "_paged_call"):
            if name.startswith("%" + kernel):
                where.setdefault(kernel, set()).add(
                    S.part_of(meta[S.PATH_STAT]))
    assert where == {"gdn_step": {"mixer/step"},
                     "gdn_chunk": {"mixer/chunk"},
                     "_paged_call": {"attn/read"}}
    for kernel, part in (("gdn_step", "mixer/step"),
                         ("gdn_chunk", "mixer/chunk"),
                         ("_paged_call", "attn/read")):
        alone = sum(s for name, s in by_name["op_seconds"].items()
                    if kernel in name)
        assert 0 < alone <= reduced["parts"][part]["self_s"]


def test_the_compiler_s_counts_ride_along(reduced):
    parts = reduced["parts"]
    # The vocabulary product and the feed-forward count FLOPs; everything
    # that ran moved bytes.
    assert parts["head"]["flops"] > 0 and parts["mlp"]["flops"] > 0
    assert all(p["bytes"] > 0 for part, p in parts.items()
               if p["ops"] and part != S.UNSCOPED)


def test_tick_programs_are_told_by_width(reduced):
    modules = reduced["modules"]
    assert set(modules) == {"tick_w1", "tick_w64"}
    assert len(modules["tick_w64"]) == 1 and len(modules["tick_w1"]) >= 1
    assert all(ms > 0 for runs in modules.values() for ms in runs)
    assert S.tick_width("tick_w64") == ("tick", 64)


def test_the_table_lists_every_part_and_program(reduced):
    lines = S.table(reduced)
    text = "\n".join(lines)
    for part in (*S.STEP_PARTS, S.UNSCOPED, "tick_w1", "tick_w64"):
        assert any(line.split()[:1] == [part] for line in lines), part
    assert "% busy" in text and "GFLOP" in text and "median ms" in text
    assert "the longest ops" in text and "%gdn_chunk (tuple)" in text


# -- bytes encoded here --------------------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    """An int as a varint, bytes / str / a message's bytes length-delimited,
    a float as a fixed64 double."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(number << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _stat(metadata_id, **value):
    (kind, v), = value.items()
    number = {"double": 2, "uint64": 3, "int64": 4, "str": 5, "bytes": 6,
              "ref": 7, "unknown": 15}[kind]
    return _field(1, metadata_id) + _field(number, v)


def _entry(key, message):
    return _field(1, key) + _field(2, message)


# stat metadata ids
TF_OP, FLOPS, BYTES, CATEGORY, A_PATH, NEW_KIND = 1, 2, 3, 4, 5, 6
STAT_NAMES = {TF_OP: "tf_op", FLOPS: "flops", BYTES: "bytes_accessed",
              CATEGORY: "hlo_category",
              # a `ref` stat's value is the NAME of this metadata
              A_PATH: "jit(tick_w1)/while/body/closed_call/mlp/dot_general:",
              NEW_KIND: "a_stat_of_a_later_format"}
#  name, start_ps, duration_ps, metadata stats
OPS = [
    ("%while.1 = (s32[]) while(...)", 1_000_000, 9_000_000,
     [_stat(TF_OP, str="jit(tick_w1)/while:")]),
    # nested in the while: its body's ops
    ("%fusion.1 = bf16[8,128] fusion(...)", 2_000_000, 3_000_000,
     [_stat(TF_OP, str="jit(tick_w1)/while/body/closed_call/attn/read/"
                       "dot_general:"),
      _stat(FLOPS, uint64=1000), _stat(BYTES, int64=64),
      _stat(NEW_KIND, unknown=7)]),
    ("%fusion.2 = bf16[8,128] fusion(...)", 5_000_000, 2_000_000,
     [_stat(TF_OP, ref=A_PATH), _stat(FLOPS, double=500.0)]),
    # after the while
    ("%copy.3 = bf16[8,128] copy(...)", 11_000_000, 1_000_000, []),
    ("%fusion.4 = f32[8] fusion(...)", 12_000_000, 4_000_000,
     [_stat(TF_OP, str="jit(tick_w1)/attn_like/read/add:"),
      _stat(BYTES, uint64=32)]),
    ("%fusion.5 = s32[8] fusion(...)", 16_000_000, 2_000_000,
     [_stat(TF_OP, str="jit(tick_w1)/sample/vmap(reveal)/sin:")]),
]
MODULES = [("jit_tick_w1(77)", 999_000, 17_001_000),   # ahead of its first op
           ("jit_tick_w1(77)", 20_000_000, 3_000_000),
           ("jit_tick_w256(78)", 30_000_000, 500_000)]


def _plane(name, ops=OPS, modules=MODULES):
    event_metadata, op_events, module_events = b"", b"", b""
    for k, (op, start, dur, stats) in enumerate(ops, start=1):
        meta = _field(1, k) + _field(2, op) + b"".join(
            _field(5, s) for s in stats)
        event_metadata += _field(4, _entry(k, meta))
        op_events += _field(4, _field(1, k) + _field(2, start)
                            + _field(3, dur))
    for k, (module, start, dur) in enumerate(modules, start=100):
        event_metadata += _field(4, _entry(k, _field(1, k)
                                           + _field(2, module)))
        module_events += _field(4, _field(1, k) + _field(2, start)
                                + _field(3, dur))
    stat_metadata = b"".join(
        _field(5, _entry(k, _field(1, k) + _field(2, text)))
        for k, text in STAT_NAMES.items())
    lines = (_field(3, _field(1, 1) + _field(2, "XLA Modules")
                    + module_events)
             + _field(3, _field(1, 2) + _field(2, "XLA Ops") + op_events))
    return _field(2, name) + lines + event_metadata + stat_metadata


@pytest.fixture(scope="module")
def made_up(tmp_path_factory):
    path = tmp_path_factory.mktemp("scopes") / "made_up.xplane.pb"
    path.write_bytes(_field(1, _plane("/device:TPU:0"))
                     + _field(1, _plane("/host:CPU")))
    return str(path)


def test_the_wire_reader_takes_every_kind_of_stat(made_up):
    stats = S.read_op_stats(made_up)
    assert list(stats) == ["/device:TPU:0"]          # the host plane is not
    ops = stats["/device:TPU:0"]
    assert ops[OPS[1][0]] == {
        "tf_op": "jit(tick_w1)/while/body/closed_call/attn/read/"
                 "dot_general:",
        "flops": 1000, "bytes_accessed": 64,
        "a_stat_of_a_later_format": None}
    # a ref's value is the stat metadata's name; a double is a double
    assert ops[OPS[2][0]] == {"tf_op": STAT_NAMES[A_PATH], "flops": 500.0}
    assert ops[OPS[3][0]] == {}
    assert ops["jit_tick_w1(77)"] == {}


def test_jax_reads_the_same_bytes_and_the_names_join(made_up):
    """What the reduction rests on: ProfileData's event name IS the
    metadata's name."""
    events = X.read_planes(made_up)["/device:TPU:0"]
    assert [(n, s, d) for n, s, d in events] == [
        (op, start / 1e3, dur / 1e3) for op, start, dur, _ in OPS]
    assert {n for n, _, _ in events} <= set(
        S.read_op_stats(made_up)["/device:TPU:0"])


def test_made_up_trace_by_part(made_up):
    out = S.reduce_file(made_up)
    parts = {part: p for part, p in out["parts"].items() if p["ops"]}
    # the while keeps what its body leaves: 9 - 3 - 2 us, with no part;
    # the copy has no tf_op; `attn_like` is no part
    assert parts[S.UNSCOPED]["self_s"] == pytest.approx(
        (4000 + 1000 + 4000) * 1e-9)
    assert parts[S.UNSCOPED]["ops"] == 3
    assert parts[S.UNSCOPED]["bytes"] == 32
    assert parts["attn/read"] == {"self_s": pytest.approx(3000e-9),
                                  "flops": 1000.0, "bytes": 64.0, "ops": 1}
    assert parts["mlp"] == {"self_s": pytest.approx(2000e-9),
                            "flops": 500.0, "bytes": 0.0, "ops": 1}
    assert parts["sample/reveal"]["self_s"] == pytest.approx(2000e-9)
    assert set(parts) == {S.UNSCOPED, "attn/read", "mlp", "sample/reveal"}
    by_name = X.reduce_file(made_up)
    assert sum(p["self_s"] for p in out["parts"].values()) == pytest.approx(
        sum(by_name["op_seconds"].values()), abs=1e-15)
    assert out["busy_s"] == by_name["busy_s"] == pytest.approx(16000e-9)
    # every run the module line holds, by program, in milliseconds
    assert out["modules"] == {
        "tick_w1": [pytest.approx(17.001e-3), pytest.approx(3e-3)],
        "tick_w256": [pytest.approx(0.5e-3)]}
    # the longest ops, named as `breakdown.device_ops` names them
    assert out["longest"][:3] == [
        ["%while (tuple)", S.UNSCOPED, pytest.approx(4000e-9)],
        ["%fusion f32[8]", S.UNSCOPED, pytest.approx(4000e-9)],
        ["%fusion bf16[8,128]", "attn/read", pytest.approx(3000e-9)]]
    assert ["%fusion bf16[8,128]", "mlp", pytest.approx(2000e-9)] in out[
        "longest"]
    # what `unscoped` holds, longest first, by op and path
    assert out["unscoped"] == [
        ["%while", "while", pytest.approx(4000e-9)],
        ["%fusion", "attn_like/read/add", pytest.approx(4000e-9)],
        ["%copy", "", pytest.approx(1000e-9)]]


def test_a_trace_with_no_part_anywhere_reads_nothing(tmp_path):
    """A parent's program: ops with paths, none of them a part's."""
    ops = [(name, start, dur,
            [_stat(TF_OP, str="jit(mixed_step)/while/body/dot_general:")]
            if stats else [])
           for name, start, dur, stats in OPS]
    path = tmp_path / "parent.xplane.pb"
    path.write_bytes(_field(1, _plane("/device:TPU:0", ops)))
    assert S.read_op_stats(str(path))["/device:TPU:0"][OPS[1][0]]
    assert S.reduce_file(str(path)) is None
    # and a trace whose device ran nothing
    path.write_bytes(_field(1, _plane("/device:TPU:0", [], [])))
    assert S.reduce_file(str(path)) is None


def test_the_compiler_s_own_grouped_product_goes_to_the_experts(tmp_path):
    """XLA expands `lax.ragged_dot` into custom calls it names itself, the
    scope path dropped (solve's first traced run, PR 55): the reader knows
    that one name. It does not make a parent's trace one with parts."""
    product = ("%ragged-dot-none.3 = f32[1792,2816] custom-call(...)",
               20_000_000, 6_000_000, [_stat(TF_OP, str="ragged-dot-none:"),
                                       _stat(FLOPS, uint64=7)])
    sizes = ("%ragged-dot-metadata.3 = (s32[65]) custom-call(...)",
             19_000_000, 1_000_000, [_stat(TF_OP, str="ragged-dot-metadata")])
    path = tmp_path / "routed.xplane.pb"
    path.write_bytes(_field(1, _plane("/device:TPU:0",
                                      [*OPS, sizes, product])))
    out = S.reduce_file(str(path))
    assert out["parts"]["moe/experts"] == {
        "self_s": pytest.approx(7000e-9), "flops": 7.0, "bytes": 0.0,
        "ops": 2}
    assert [op for op, _, _ in out["unscoped"]] == ["%while", "%fusion",
                                                    "%copy"]
    assert S.part_of("ragged-dot-none:") is None
    assert S.compiler_named("ragged-dot-none:") == "moe/experts"
    assert S.compiler_named("jit(tick_w1)/ragged-dot-none") is None
    # the parent: the same two ops and no path of the program's
    path.write_bytes(_field(1, _plane("/device:TPU:0", [sizes, product])))
    assert S.reduce_file(str(path)) is None


def test_one_instruction_text_under_two_parts_counts_as_the_first(tmp_path):
    """Two programs of one plane may hold the same instruction text; an
    event carries the text alone, so both count under the part first read
    and the reduction says how many such texts there were."""
    again = (OPS[1][0], 20_000_000, 1_000_000,
             [_stat(TF_OP, str="jit(tick_w256)/head/dot_general:")])
    same = (OPS[2][0], 22_000_000, 1_000_000, [_stat(TF_OP, ref=A_PATH)])
    path = tmp_path / "twice.xplane.pb"
    path.write_bytes(_field(1, _plane("/device:TPU:0", [*OPS, again, same])))
    out = S.reduce_file(str(path))
    assert out["collisions"] == 1
    assert out["parts"]["attn/read"]["self_s"] == pytest.approx(4000e-9)
    assert out["parts"]["attn/read"]["flops"] == 2000.0
    assert out["parts"]["head"]["ops"] == 0
    assert out["parts"]["mlp"]["ops"] == 2
    assert S.reduce_file(TRACE)["collisions"] == 0


def test_two_planes_average_as_the_reducer_s(tmp_path):
    path = tmp_path / "two.xplane.pb"
    path.write_bytes(_field(1, _plane("/device:TPU:0"))
                     + _field(1, _plane("/device:TPU:1", OPS[:3], []))
                     + _field(1, _plane("/device:TPU:2", [], [])))
    out, by_name = S.reduce_file(str(path)), X.reduce_file(str(path))
    assert out["planes"] == by_name["planes"] == 2
    assert out["busy_s"] == by_name["busy_s"]
    assert out["parts"]["attn/read"]["self_s"] == pytest.approx(3000e-9)
    assert out["parts"]["attn/read"]["flops"] == 1000.0   # 2 runs / 2 planes
    assert out["parts"]["sample/reveal"]["self_s"] == pytest.approx(1000e-9)


@pytest.mark.parametrize("path, part", [
    ("jit(tick_w256)/while/body/closed_call/attn/read/dot_general:",
     "attn/read"),
    ("jit(tick_w256)/attn/qkv/mul", "attn/qkv"),         # no `:type`
    ("jit(tick_w1)/moe/route/sort:", "moe/route"),
    ("jit(tick_w1)/moe/experts/cond/branch_1_fun/mul:", "moe/experts"),
    ("jit(tick_w1)/moe/shared/mlp/dot_general:", "moe/shared"),  # outermost
    ("jit(tick_w1)/mixer/chunk/while/body/mixer/step/add:", "mixer/chunk"),
    ("jit(tick_w1_r4)/sample/vmap(reveal)/sin:", "sample/reveal"),
    ("jit(tick_w1)/jvp(vmap(sample))/add:", "sample"),
    ("jit(tick_w1)/sample/cond/branch_2_fun/sort:", "sample"),
    ("jit(tick_w1)/head/dot_general:", "head"),
    ("jit(tick_w1)/attn/while/body/read/mul:", "attn/read"),
    ("jit(tick_w1)/attn/mul:", None),          # `attn` alone is no part
    ("jit(tick_w1)/attn_like/read/add:", None),
    ("jit(tick_w1)/reading/headroom/add:", None),
    ("jit(tick_w1)/while/body/dynamic_slice:", None),
    ("jit(<unknown>)/gather:", None),
    ("", None),
])
def test_part_of_takes_whole_components_outermost_first(path, part):
    assert S.part_of(path) == part


@pytest.mark.parametrize("event, module, tick", [
    ("jit_tick_w256(1234)", "tick_w256", ("tick", 256)),
    ("jit_tick_w1(5)", "tick_w1", ("tick", 1)),
    ("jit_tick_w1_r4(99)", "tick_w1_r4", ("tick", 1)),
    ("jit_tick_w16_r4(99)", "tick_w16_r4", ("tick", 16)),
    ("jit_spec_w5(7)", "spec_w5", ("spec", 5)),
    ("jit_mixed_step(42)", "mixed_step", None),
    ("jit__where(3)", "_where", None),
    ("tick_w12", "tick_w12", ("tick", 12)),
])
def test_module_names(event, module, tick):
    assert S.module_name(event) == module
    assert S.tick_width(module) == tick


def test_the_vocabulary_is_the_program_s():
    """The benchmark spells the parts itself, as lib/host_phases.py spells
    the tick's phases; the two tuples are one."""
    from bench_paths import ROOT

    sys.path.insert(0, ROOT)
    from tpu_engine.utils import tracing

    assert S.STEP_PARTS == tracing.STEP_PARTS
    assert S.UNSCOPED not in S.STEP_PARTS
