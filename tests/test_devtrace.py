"""Reading a device trace against the program's spans and named scopes
(``obs.devtrace``): a hand-made trace whose answers are known exactly, and
the scopes the compiled serving programs carry in their HLO ``op_name``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import devtrace

HOST, DEV, OPS = "/host:CPU", "/device:TPU:0", devtrace.OPS_LINE
STEP = "jit(step)/layers/while/body/closed_call"


def _mark(name, sid, a, b):
    return (HOST, "python3", name, a, b - a, {"sid": sid})


def _op(name, a, b, plane=DEV):
    return (plane, OPS, name, a, b - a, {})


def _prog(name, a, b):
    return (DEV, devtrace.MODULES_LINE, name, a, b - a, {})


EVENTS = [
    _mark("engine_step", 1, 0, 100), _mark("decode_step", 2, 10, 100),
    _mark("grow", 3, 10, 15), _mark("build", 4, 15, 20),
    _mark("dispatch", 5, 20, 30), _mark("sample", 6, 30, 90),
    _mark("sync", 7, 35, 90), _mark("emit", 8, 90, 98),
    _mark("gc", 9, 92, 96),
    (HOST, "python3", "sb.step", 0, 100, {}),          # no sid: not a span
    _prog("jit_zero(3)", 0, 5),
    _op("fusion.9", 0, 5),
    _prog("jit_step(7)", 22, 81),
    _op("while.1", 22, 80),
    _op("fusion.1", 22, 40),
    _op("fusion.2", 40, 60),
    _op("copy.3", 60, 70),
    _op("%fusion.4 = bf16[8] fusion(%x), calls=%f", 72, 80),  # HLO text
    _prog("jit_sample(9)", 81, 89),
    _op("sort.5", 81, 88),
    _op("fusion.4", 88, 89),                      # another program's fusion.4
    _op("fusion.7", 30, 60, plane="/device:TPU:1"),
]
NAMES = {"jit_zero(3)": {"fusion.9": "jit(zero)/dynamic_update_slice"},
         "jit_step(7)": {"while.1": "jit(step)/layers/while",
                         "fusion.1": STEP + "/attn/paged_gather/dot_general",
                         "fusion.2": STEP + "/mlp/dot_general",
                         "copy.3": STEP + "/dynamic_slice",
                         "fusion.4": "jit(step)/head/dot_general"},
         "jit_sample(9)": {"sort.5": "jit(sample_stateless)/sample/sort",
                           "fusion.4": None}}


def test_hand_made_trace_joins_spans_and_scopes():
    tr = devtrace.from_events(EVENTS, NAMES)
    assert [m.sid for m in tr.marks] == [1, 2, 3, 4, 5, 6, 7, 8, 9]
    assert tr.by_sid[7].name == "sync"
    assert [o.scope for o in tr.ops] == ["unscoped", "layers", "attn", "mlp",
                                         "layers", "head", "sample",
                                         "unscoped"]
    # without the programs' HLO, no operation has a scope
    assert {o.scope for o in devtrace.from_events(EVENTS).ops} == \
        {"unscoped"}
    assert tr.gaps(0, 100) == [(5, 22), (80, 81), (89, 100)]
    assert tr.innermost(94).name == "gc" and tr.innermost(50).name == "sync"
    assert tr.innermost(-1) is None


def test_hand_made_trace_step_parts():
    parts = devtrace.step_parts(devtrace.from_events(EVENTS, NAMES))
    want = {"attn": 18, "mlp": 20, "layers": 10, "head": 8, "sample": 7,
            "unscoped": 1, "ssm": 0, "idle": 12 + 1 + 11}
    assert parts == pytest.approx({k: v * 1e-6 for k, v in want.items()})
    assert devtrace.step_parts(devtrace.from_events(EVENTS), "none") == {}


def test_hand_made_trace_idle_gaps_by_leaf():
    gaps = devtrace.idle_gaps(devtrace.from_events(EVENTS, NAMES))
    assert [(g["ms"], g["leaf"]) for g in gaps] == [
        (pytest.approx(17e-6), "grow"), (pytest.approx(11e-6), "gc"),
        (pytest.approx(1e-6), "sync")]
    assert gaps[0]["cover"] == {"engine_step": 5, "grow": 5, "build": 5,
                                "dispatch": 2}
    assert gaps[1]["cover"] == {"sync": 1, "emit": 4, "gc": 4,
                                "decode_step": 2}
    assert gaps[1]["sid"] == 9
    long_ones = devtrace.idle_gaps(devtrace.from_events(EVENTS), top=1,
                                   min_ns=12)
    assert [g["leaf"] for g in long_ones] == ["grow"]


@pytest.mark.parametrize("op_name,scope", [
    ("jit(paged_step)/layers/while/body/closed_call/attn/paged_gather/"
     "jit(paged_gather_pallas)/while/body/add", "attn"),
    ("jit(paged_step)/layers/while/body/closed_call/mlp/dot_general", "mlp"),
    ("jit(paged_step)/layers/while/body/dynamic_slice", "layers"),
    ("jit(paged_step)/head/dot_general", "head"),
    ("jit(sample_stateless)/sample/sort", "sample"),
    ("jit(paged_step)/convert_element_type", "unscoped"),
    ("jit(attn_helper)/mul", "unscoped"),       # a scope is a whole part
    ("", "unscoped"), (None, "unscoped"),
])
def test_scope_of(op_name, scope):
    assert devtrace.scope_of(op_name) == scope


HLO = """HloModule jit_step

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%p), index=1
  %copy.7 = f32[8]{0} copy(%gte.1)
  %fusion.2 = f32[8]{0} fusion(%copy.7), kind=kLoop, calls=%fc, \
metadata={op_name="jit(step)/layers/while/body/attn/mul"}
  %copy.8 = f32[8]{0} copy(%fusion.2)
  ROOT %t = (s32[], f32[8]{0}) tuple(%gte.1, %copy.8)
}

ENTRY %main.3 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %while.4 = (s32[], f32[8]{0}) while(%x), condition=%cond, body=%body, \
metadata={op_name="jit(step)/layers/while" stack_frame_id=9}
  %gte.9 = f32[8]{0} get-tuple-element(%while.4), index=1
  ROOT %copy.82 = f32[8]{0} copy(%gte.9)
}
"""


def test_op_names_reads_compiled_hlo_text():
    names = devtrace.op_names(HLO)
    assert names["fusion.2"] == "jit(step)/layers/while/body/attn/mul"
    assert names["while.4"] == "jit(step)/layers/while"
    # compiler-made copies: from their operand, else from the loop
    assert names["copy.8"] == names["fusion.2"]           # attn
    assert names["copy.82"] == "jit(step)/layers/while"   # the loop's carry
    assert names["copy.7"] == "jit(step)/layers/while"    # the loop's input
    assert names["x"] is None
    assert devtrace.base_name("%fusion.12 = f32[8] fusion(x)") == "fusion"
    assert devtrace.instruction("%fusion.12 = f32[8] fusion(%x)") == \
        "fusion.12"


def test_programs_hlo_is_read_from_the_trace(tmp_path):
    @jax.jit
    def f(x):
        with jax.named_scope("attn"):
            y = jnp.sin(x) @ x
        with jax.named_scope("mlp"):
            return jnp.tanh(y) + 1
    x = jnp.ones((64, 64))
    jax.profiler.start_trace(str(tmp_path))
    try:
        f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    import glob
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    texts = devtrace.hlo_texts(path)
    prog, = [p for p in texts if p.startswith("jit_f(")]
    scopes = {devtrace.scope_of(n)
              for n in devtrace.op_names(texts[prog]).values()}
    assert {"attn", "mlp"} <= scopes


def _paged_step_scopes(attn):
    from repro.configs import registry
    from repro.models import transformer as T
    from repro.serving import Engine
    kw = {"attn_impl": "srf"} if attn == "srf" else {}
    cfg = registry.reduced("qwen3-4b", n_layers=2, **kw)
    eng = Engine(cfg, T.init(jax.random.PRNGKey(0), cfg), batch_slots=2,
                 max_len=64)
    b, m = eng.sched_cfg.max_batch, eng.sched_cfg.table_width
    z = jnp.zeros((b, 1), jnp.int32)
    text = eng._step.lower(eng.params, eng.pools, z, z, z.astype(bool),
                           jnp.zeros((b, m), jnp.int32),
                           jnp.zeros((b,), jnp.int32)).compile().as_text()
    return [devtrace.scope_of(n) for n in devtrace.op_names(text).values()]


@pytest.mark.parametrize("attn", ["kv", "srf"])
def test_paged_step_carries_layer_scopes(attn):
    scopes = _paged_step_scopes(attn)
    assert {"attn", "mlp", "layers", "head"} <= set(scopes)
    assert "sample" not in scopes
    # nearly every instruction of the step lies in some scope
    assert scopes.count("unscoped") < 0.2 * len(scopes)


@pytest.mark.parametrize("name", ["sample", "sample_stateless",
                                  "greedy_tokens"])
def test_samplers_carry_the_sample_scope(name):
    from repro.serving import sampler
    b, v = 2, 16
    f32, i32 = jnp.float32, jnp.int32
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(b, v)), f32)
    knobs = (jnp.zeros((b,), f32), jnp.zeros((b,), i32), jnp.ones((b,), f32))
    lead = ((jax.random.PRNGKey(0),) if name == "sample" else
            (jax.random.PRNGKey(0), jnp.arange(b, dtype=jnp.uint32),
             jnp.zeros((b,), i32)))
    if name == "greedy_tokens":
        lead, knobs = (), ()
    fn = getattr(sampler, name)
    text = fn.lower(*lead, logits, *knobs).compile().as_text()
    # reducers and comparators inside sort/reduce carry a bare primitive
    # name; every instruction traced from the function carries its path
    scopes = {devtrace.scope_of(n)
              for n in devtrace.op_names(text).values() if n and "/" in n}
    assert scopes == {"sample"}
    np.testing.assert_array_equal(np.asarray(fn(*lead, logits, *knobs)),
                                  np.argmax(np.asarray(logits), -1))
