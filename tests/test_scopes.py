"""Names on the device work and spans on one clock (ISSUE 26): the
scope vocabulary reaches the compiled HLO of both train steps, forward
and backward; the program's own map from instruction to scope covers
the program; the ``mx.*`` spans nest in the profiler's ring on
``time.perf_counter()``; a new shape gives exactly one more
``mx.compile``; the ring is bounded."""
import re
import time

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import diagnostics, gluon, profiler, traceview
from mxnet_tpu.gluon.model_zoo.vision import resnet
from mxnet_tpu.parallel.dp import FusedTrainStep
from mxnet_tpu.parallel.mesh import make_mesh
from mxnet_tpu.transformer import TransformerConfig, TransformerTrainStep

# names that the forward pass and its transpose both carry, and names
# outside the differentiated function (or, an addition, with nothing
# left of its transpose)
LM_BOTH = ["embed", "norm", "attn_proj", "attn", "mlp", "head_loss",
           "layer00", "layer01"]
RESNET_BOTH = ["Convolution", "BatchNorm", "Activation", "Pooling",
               "FullyConnected", "resnetv10_stage1",
               "resnetv10_stage1_batchnorm0"]
PLAIN = {"lm": ["optimizer"],
         "resnet": ["optimizer", "cast", "_binary_add"]}


def _mesh():
    return make_mesh((1,), ("dp",), jax.devices()[:1])


def _lm_batch(seq):
    tok = np.random.RandomState(0).randint(0, 64, (2, seq + 1))
    return tok[:, :-1].astype("int32"), tok[:, 1:].astype("int32")


def _lm_step():
    cfg = TransformerConfig(vocab_size=64, n_layers=2, d_model=32,
                            n_heads=2, d_ff=64)
    return TransformerTrainStep(cfg, mesh=_mesh(), learning_rate=0.1,
                                momentum=0.9, attn_impl="flash",
                                remat="block", seed=0)


def _compiled(name):
    """(optimized HLO text, its scope map, the argument specs the step
    was compiled for) after two steps of the toy program."""
    diagnostics.reset_recompile_stats()
    if name == "lm":
        step, key = _lm_step(), "TransformerTrainStep.step"
        for _ in range(2):
            loss = step.step(*_lm_batch(16))
    else:
        # the prefix pinned: gluon numbers a second ResNet of one
        # process ``resnetv11_``, and RESNET_BOTH names ``resnetv10_``
        net = resnet.resnet18_v1(classes=10, prefix="resnetv10_")
        net.initialize(mx.init.Xavier())
        step, key = FusedTrainStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), mesh=_mesh(),
            learning_rate=0.1, momentum=0.9), "FusedTrainStep.step"
        # a uint8 batch, as an image pipeline hands it over: the cast
        # to the compute dtype happens inside the program
        x = mx.nd.array(np.random.RandomState(0).randint(
            0, 255, (4, 3, 32, 32)).astype("uint8"), dtype="uint8")
        y = mx.nd.array((np.arange(4) % 10).astype("float32"))
        for _ in range(2):
            loss = step(x, y)[0]._data
    jax.block_until_ready(loss)
    wrapper, specs, _ = diagnostics.recorded_steps()[key]
    text = wrapper.lower(*specs).compile().as_text()
    maps = traceview.program_scopes()
    return text, maps[traceview.parse_hlo_scopes(text)[0]], specs


@pytest.fixture(scope="module", params=["lm", "resnet"])
def program(request):
    return (request.param,) + _compiled(request.param)


def _op_names(text):
    return re.findall(r'op_name="([^"]*)"', text)


def test_compiled_hlo_holds_the_vocabulary_forward_and_backward(program):
    name, text, _, _ = program
    paths = [(n, traceview.scope_path(n)) for n in _op_names(text)]
    forward = {p for n, path in paths if "transpose(" not in n
               for p in path}
    backward = {p for n, path in paths if "transpose(" in n for p in path}
    both = LM_BOTH if name == "lm" else RESNET_BOTH
    assert not [v for v in both if v not in forward], sorted(forward)
    assert not [v for v in both if v not in backward], sorted(backward)
    assert not [v for v in PLAIN[name] if v not in forward]


def test_the_programs_map_names_nine_instructions_in_ten(program):
    name, text, scopes, _ = program
    assert len(scopes) > 100
    named = [k for k, v in scopes.items() if traceview.scope_path(v)]
    assert len(named) >= 0.9 * len(scopes), (
        name, len(named), len(scopes),
        [k for k in scopes if k not in named][:20])
    # and most of them by their own metadata, not their neighbour's
    # (the CPU compiler's own layout copies of every convolution weight
    # and its relaid convolutions carry none: 63 % on the ResNet)
    own = set(re.findall(r'%([\w.\-]+) = [^\n]*op_name="jit\(', text))
    assert len(own & set(scopes)) >= 0.6 * len(scopes)


_HLO_LINE = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(\(.*?\)|\S+)\s+([\w\-]+)\(")
_ARRAY = re.compile(r"\w+\[([\d,]*)\]")


def _elements(shape):
    """Elements of the largest array an instruction produces."""
    return max([int(np.prod([int(d) for d in dims.split(",") if d]))
                for dims in _ARRAY.findall(shape)] or [0])


def test_optimizer_updates_each_leaf_where_it_lies(program):
    """ISSUE 27: under the ``optimizer`` scope nothing is packed into a
    flat (no ``concatenate``, no ``dynamic-update-slice``, nothing
    larger than the largest leaf), and every donated parameter and
    momentum is aliased to an output."""
    name, text, scopes, specs = program
    leaves = jax.tree_util.tree_leaves(specs[:2])
    largest = max(int(np.prod(leaf.shape)) for leaf in leaves)
    # what runs under the scope by the program's own map (an unscoped
    # copy takes its reader's), and what a fusion under it fused
    lines = {m.group(1): (m.group(3), m.group(2), line)
             for m, line in ((_HLO_LINE.match(line), line)
                             for line in text.splitlines()) if m}
    under = {k for k, v in scopes.items()
             if "optimizer" in traceview.scope_path(v)}
    under |= {k for k, (_, _, line) in lines.items()
              if "optimizer" in traceview.scope_path(
                  "".join(_op_names(line)))}
    assert len(under) >= len(leaves) // 2       # one a parameter
    for inst in sorted(under):
        opcode, shape, line = lines[inst]
        assert opcode not in ("concatenate", "dynamic-update-slice"), (
            name, line[:200])
        assert _elements(shape) <= largest, (name, line[:200])
    aliased = set(map(int, re.findall(
        r"\((\d+), \{\}, (?:may|must)-alias\)", text.splitlines()[0])))
    assert set(range(len(leaves))) <= aliased, (
        name, sorted(set(range(len(leaves))) - aliased))


def test_scope_path_strips_transforms_and_primitives():
    path = traceview.scope_path
    assert path("jit(step)/transpose(jvp(layer03))/jvp(layer03)/"
                "checkpoint/attn/while/body/mul") == (
        "layer03", "checkpoint", "attn", "while", "body")
    # the operator ``transpose`` is a scope, the primitive is not
    assert path("jit(f)/transpose(jvp(transpose))/transpose") == (
        "transpose",)
    assert path("jit(step)/jvp(net)/net/net/net_conv0/Convolution/"
                "jit(<unknown>)/conv_general_dilated") == (
        "net", "net_conv0", "Convolution")
    assert path("reduce_sum") == () and path("") == ()


def test_parse_hlo_scopes_lists_what_runs_and_not_what_is_fused():
    program, scopes = traceview.parse_hlo_scopes("""\
HloModule jit_step, entry_computation_layout={()->f32[]}

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %mul.1 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(step)/mlp/mul"}
}

%region_0.3 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="reduce_sum"}
}

%body (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]{0}) parameter(0)
  %x = f32[4]{0} get-tuple-element(%t), index=1
  %fusion.7 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/layer00/attn/while/body/mul"}
  ROOT %tuple.2 = (s32[], f32[4]{0}) tuple(%x, %fusion.7)
}

ENTRY %main.5 (arg: f32[4], w: f32[4]) -> f32[] {
  %arg = f32[4]{0} parameter(0)
  %w = f32[4]{0} parameter(1), metadata={op_name="w"}
  %copy.1 = f32[4]{0} copy(%arg)
  %copy.2 = f32[4]{0} copy(%w), metadata={op_name="w"}
  %bitcast.6 = f32[2,2]{1,0} bitcast(%copy.2)
  %while.3 = (s32[], f32[4]{0}) while(%copy.1), condition=%cond, body=%body, metadata={op_name="jit(step)/layer00/attn/while"}
  %dot.8 = f32[2,2]{1,0} dot(%bitcast.6, %bitcast.6), metadata={op_name="jit(step)/jvp(layer00)/mlp/dot_general"}
  %copy.10 = f32[2,2]{1,0} copy(%dot.8)
  %copy.11 = f32[4]{0} copy(%arg)
  ROOT %reduce.4 = f32[] reduce(%copy.1, %arg), dimensions={0}, to_apply=%region_0.3, metadata={op_name="jit(step)/head_loss/reduce_sum"}
}
""")
    assert program == "jit_step"
    assert scopes == {
        "fusion.7": "jit(step)/layer00/attn/while/body/mul",
        "while.3": "jit(step)/layer00/attn/while",
        "dot.8": "jit(step)/jvp(layer00)/mlp/dot_general",
        "reduce.4": "jit(step)/head_loss/reduce_sum",
        # made by the compiler, with no scope: its first reader's
        "copy.1": "jit(step)/layer00/attn/while",
        # ... through a bitcast, though it has an op_name (no scope)
        "copy.2": "jit(step)/jvp(layer00)/mlp/dot_general",
        # ... nobody reads it: what it reads from
        "copy.10": "jit(step)/jvp(layer00)/mlp/dot_general",
        # ... neither
        "copy.11": ""}


def _mx_spans(t0):
    return [s for s in profiler.spans_between(t0, time.perf_counter())
            if s.name.startswith("mx.")]


def test_ring_nests_on_perf_counter_and_counts_compiles():
    diagnostics.reset_recompile_stats()
    step = _lm_step()
    t0 = time.perf_counter()
    for _ in range(3):
        step.step(*_lm_batch(16))
    t1 = time.perf_counter()
    spans = _mx_spans(t0)
    steps = [s for s in spans if s.name == "mx.step"]
    assert len(steps) == 3
    for outer in steps:
        assert outer.depth == 0 and t0 <= outer.t0 <= outer.t1 <= t1
        inner = [s for s in spans if outer.t0 <= s.t0 and s.t1 <= outer.t1
                 and s is not outer]
        assert [s.name for s in inner if s.name != "mx.compile"] == [
            "mx.step.feed", "mx.step.launch"]
        assert all(s.depth == 1 and s.thread == outer.thread
                   for s in inner)
    compiles = [s for s in spans if s.name == "mx.compile"]
    assert len(compiles) == 1
    assert compiles[0].args == {"step": "TransformerTrainStep.step"}
    launch = [s for s in spans if s.name == "mx.step.launch"][0]
    assert compiles[0].t0 == launch.t0 and compiles[0].t1 >= launch.t1
    # a second shape: exactly one more compile, and none after it
    t2 = time.perf_counter()
    for _ in range(2):
        step.step(*_lm_batch(24))
    assert len([s for s in _mx_spans(t2) if s.name == "mx.compile"]) == 1


def test_run_steps_is_one_step_span_numbered_from_the_first_step():
    step = _lm_step()
    tok, lab = _lm_batch(16)
    step.step(tok, lab)
    t0 = time.perf_counter()
    step.run_steps(tok, lab, 3)
    assert [s.name for s in _mx_spans(t0) if s.depth == 0] == ["mx.step"]
    assert step._step_no == 4


def test_ring_is_bounded_and_spans_reach_the_chrome_dump(tmp_path):
    for _ in range(profiler.RING_SPANS + 10):
        profiler.record_interval("filler", 0.0, 0.0)
    assert len(profiler.spans_between(-1.0, 1e18)) == profiler.RING_SPANS
    profiler.set_config(filename=str(tmp_path / "p.json"))
    profiler.set_state("run")
    with profiler.span("mx.tick", cat="serving",
                       args={"live": 3, "slots": 4}):
        pass
    profiler.set_state("stop")
    last = profiler.spans_between(0.0, 1e18)[-1]
    assert last.name == "mx.tick" and last.args == {"live": 3, "slots": 4}
    assert '"mx.tick"' in (tmp_path / "p.json").read_text()
