"""The split of device time by the program's kernel names and scopes: a
hand-built HloProto inside a hand-built XSpace, and a CPU profile of the
smoke model's decode step."""
import json

import jax
import jax.numpy as jnp
import pytest

from bench import families, hlo_scopes

SCOPES = families.load("dense").SCOPES


def _varint(x: int) -> bytes:
    out = bytearray()
    while True:
        b, x = x & 0x7F, x >> 7
        out.append(b | (0x80 if x else 0))
        if not x:
            return bytes(out)


def msg(*fields) -> bytes:
    """Protobuf wire bytes of ``(field number, int | str | bytes)``."""
    out = b""
    for n, v in fields:
        if isinstance(v, int):
            out += _varint(n << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(n << 3 | 2) + _varint(len(v)) + v
    return out


def instr(name, opcode, op_name="", called=(), kernel=None):
    fields = [(1, name), (2, opcode), (7, msg((2, op_name)))]
    if called:          # packed, as the compiler writes repeated ints
        fields.append((38, b"".join(_varint(c) for c in called)))
    meta = "{}" if kernel is None else json.dumps({"kernel": kernel})
    fields.append((68, msg((1, msg((1, "kernel_metadata"), (2, meta))))))
    return msg(*fields)


def comp(cid, name, *instrs):
    return msg((1, name), *[(2, i) for i in instrs], (5, cid))


S = "jit(step)/while/body/closed_call"
MODULE = msg((1, msg(
    (1, "jit_step"),
    (3, comp(1, "fused_kv",
             instr("p", "parameter"),
             instr("sel", "select", f"{S}/kv_update/select_n"),
             instr("dus", "dynamic-update-slice",
                   "jit(step)/while/body/dynamic_update_slice"))),
    (3, comp(2, "fused_mixed",
             instr("sel", "select", f"{S}/kv_update/select_n"),
             instr("red", "reduce", f"{S}/attention/dot_general"))),
    (3, comp(3, "fused_inner",
             instr("exp", "exponential", f"{S}/attention/exp"))),
    (3, comp(4, "fused_outer",
             instr("f", "fusion", "", called=(3,)),
             instr("cp", "copy", ""))),
    (3, comp(5, "body",
             instr("spmm.1", "custom-call", f"{S}/pallas_call",
                   kernel="balanced_spmm_skinny"),
             instr("kv.1", "custom-call", f"{S}/kv_update/pallas_call",
                   kernel="kv_cache_update"),
             instr("other.1", "custom-call", f"{S}/attention/pallas_call",
                   kernel="some_other_kernel"),
             instr("write_fusion", "fusion", "", called=(1,)),
             instr("mixed_fusion", "fusion", "", called=(2,)),
             instr("nested_fusion", "fusion", "", called=(4,)),
             instr("head", "dot", "jit(step)/lm_head/dot_general"),
             instr("add.1", "add", f"{S}/add"))))))


def xspace(protos: dict) -> bytes:
    stat_meta = msg((1, 9), (2, msg((1, 9), (2, "Hlo Proto"))))
    events = [msg((1, i), (2, msg((1, i), (2, name),
                                  (5, msg((1, 9), (6, proto))))))
              for i, (name, proto) in enumerate(protos.items(), 1)]
    meta = msg((2, "/host:metadata"), *[(4, e) for e in events],
               (5, stat_meta))
    device = msg((1, 1), (2, "/device:TPU:0"))
    return msg((1, device), (1, meta))


def test_hlo_protos_picks_modules_by_event_name():
    data = xspace({"jit_step(7)": MODULE, "jit_other(8)": b"\x0a\x00"})
    assert set(hlo_scopes.hlo_protos(data)) == {"jit_step(7)",
                                                "jit_other(8)"}
    got = hlo_scopes.hlo_protos(data, {"jit_step(7)"})
    assert list(got) == ["jit_step(7)"] and bytes(got["jit_step(7)"]) == \
        MODULE


def test_each_top_level_instruction_goes_to_one_bucket():
    att = hlo_scopes.attribute(MODULE, SCOPES)
    assert att == {
        "spmm.1": "balanced_spmm",            # by kernel name
        "kv.1": "kv_update",                  # kernel name before scope
        "other.1": "attention",               # an unknown kernel: scope
        "write_fusion": "kv_update",          # one scope among the fused
        "mixed_fusion": "unattributed",       # two scopes
        "nested_fusion": "attention",         # through a nested fusion
        "head": "lm_head",
        "add.1": "unattributed",              # no scope
    }


def test_split_counts_every_op_once():
    att = hlo_scopes.attribute(MODULE, SCOPES)
    op_s = {"%spmm.1 = f32[8,2048] custom-call": 3.0,
            "%write_fusion = bf16[16,128,2048,128] fusion": 0.5,
            "%mixed_fusion = f32[128,2048] fusion": 0.25,
            "%head = f32[8,50304] dot": 0.125,
            "%while.4": 0.0625,                  # not in the attribution
            "%nested_fusion = f32[8] fusion": 1.0}
    out = hlo_scopes.split(op_s, att, SCOPES)
    assert out == {"balanced_spmm": 3.0, "kv_update": 0.5,
                   "attention": 1.0, "lm_head": 0.125,
                   "unattributed": 0.3125}
    assert sum(out.values()) == sum(op_s.values())


def test_unnamed_program_reads_nothing(tmp_path):
    """A module that names no kernel and no scope (the program before its
    names were added) leaves every metric out."""
    unnamed = MODULE.replace(b"kv_update", b"kv_xpdate").replace(
        b"attention", b"attentiom").replace(b"lm_head", b"lm_heed").replace(
        b"balanced_spmm_skinny", b"balanced_spmm_skinnz").replace(
        b"kv_cache_update", b"kv_cache_updatf")
    assert set(hlo_scopes.attribute(unnamed, SCOPES).values()) == {
        "unattributed"}

    class Ctx:
        summary = None
        counters = {"steps": 4}
    assert hlo_scopes.step_ms(Ctx(), "balanced_spmm") is None


def test_cpu_profile_of_a_decode_step_puts_an_op_in_each_scope(tmp_path):
    from repro.configs import get_smoke
    from repro.models import build_model
    bundle = build_model(get_smoke("olmo-1b"))
    params = bundle.init(jax.random.key(0))
    cache = bundle.init_cache(2, 16)
    batch = {"tokens": jnp.zeros((2, 1), jnp.int32),
             "cache_len": jnp.full((2,), 3, jnp.int32)}
    step = jax.jit(bundle.decode_step)
    jax.block_until_ready(step(params, batch, cache))
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(step(params, batch, cache))
    finally:
        jax.profiler.stop_trace()
    path = hlo_scopes.newest_xplane(str(tmp_path))
    with open(path, "rb") as f:
        protos = hlo_scopes.hlo_protos(f.read())
    names = [n for n in protos if n.startswith("jit_decode_step(")]
    assert len(names) == 1, sorted(protos)
    buckets = set(hlo_scopes.attribute(protos[names[0]], SCOPES).values())
    assert {"kv_update", "attention", "lm_head"} <= buckets


@pytest.mark.parametrize("phase", ["prune", "blocks", "pack", "encode"])
def test_plan_phase_readers_read_the_program_spans(phase):
    import importlib.util

    from repro import obs
    from .smoke import ROOT
    path = ROOT / "bench" / "metrics" / f"plan_{phase}_s.py"
    spec = importlib.util.spec_from_file_location(f"m_{phase}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    obs.reset()
    assert mod.read(None) is None
    with obs.span(f"plan.{phase}", layer="wq"):
        pass
    assert mod.read(None) >= 0.0
    obs.reset()
