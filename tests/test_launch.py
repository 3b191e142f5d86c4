"""Launch-layer units: sharding rules, input specs, HLO analyzer, and the
end-to-end dry-run on a 4-device debug mesh (subprocess so the forced
device count never leaks into this process)."""
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo import analyze_hlo
from repro.configs import get_config
from repro.launch.inputs import serve_batch_specs, train_batch_specs
from repro.models.config import INPUT_SHAPES

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_hlo_analyzer_scan_trip_counts():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    txt = jax.jit(f).lower(x, w).compile().as_text()
    t = analyze_hlo(txt)
    assert t.flops >= 10 * 2 * 128 * 256 * 256  # trip-count multiplied
    assert t.flops < 1.2 * 10 * 2 * 128 * 256 * 256 + 10 * 128 * 256 * 4


def test_hlo_analyzer_tuple_shapes_with_index_comments():
    from repro.analysis.hlo import _parse_instr_line
    line = ('  %while.1 = (s32[], f32[36,64]{1,0}, /*index=5*/f32[2,3]) '
            'while(%tuple.1), condition=%c, body=%b, '
            'backend_config={"known_trip_count":{"n":"7"}}')
    parsed = _parse_instr_line(line)
    assert parsed is not None
    name, shape, op, rest = parsed
    assert op == "while"
    assert "known_trip_count" in rest


# The form the TPU compiler gives a BK step's layer loops: double-buffered
# `wide.*` whiles without a known_trip_count annotation (the bound sits in
# the condition), and matmuls emitted as `convolution`.
_TPU_LOOPS_HLO = """HloModule step

%wide.cond.1 (arg: (s32[], f32[8,8])) -> pred[] {
  %constant.7 = s32[]{:T(128)} constant(4)
  %arg = (s32[]{:T(128)}, f32[8,8]{1,0}) parameter(0)
  %gte.1 = s32[]{:T(128)} get-tuple-element(%arg), index=0
  ROOT %lt.1 = pred[]{:T(512)} compare(%gte.1, %constant.7), direction=LT
}

%wide.body.1 (arg: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %arg = (s32[]{:T(128)}, f32[8,8]{1,0}) parameter(0)
  %gte.2 = f32[8,8]{1,0} get-tuple-element(%arg), index=1
  %convolution.1 = f32[8,8]{1,0} convolution(%gte.2, %gte.2), dim_labels=bf_io->bf, metadata={op_name="jit(step_fn)/%s/while/body/dot_general"}
  %gte.3 = s32[]{:T(128)} get-tuple-element(%arg), index=0
  ROOT %tuple.1 = (s32[]{:T(128)}, f32[8,8]{1,0}) tuple(%gte.3, %convolution.1)
}

ENTRY %main (p0: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p0 = (s32[]{:T(128)}, f32[8,8]{1,0}) parameter(0)
  ROOT %while.1 = (s32[]{:T(128)}, f32[8,8]{1,0}) while(%p0), condition=%wide.cond.1, body=%wide.body.1
}
"""


@pytest.mark.parametrize("scope,passes", [("transpose(jvp())", 1),
                                          ("jvp()", 0)])
def test_backward_passes_reads_tpu_layer_loops(scope, passes):
    from repro.analysis.hlo import backward_passes
    text = _TPU_LOOPS_HLO.replace("%s", scope)
    assert backward_passes(text, 4) == passes
    assert backward_passes(text, 3) == 0  # the bound must match the depth


def test_input_specs_cover_archs():
    for arch in ("qwen3-4b", "whisper-medium", "qwen2-vl-72b"):
        cfg = get_config(arch)
        specs = train_batch_specs(cfg, INPUT_SHAPES["train_4k"])
        assert specs["tokens"].shape == (256, 4096)
        if arch == "whisper-medium":
            assert "frames" in specs
        if arch == "qwen2-vl-72b":
            assert "vision_embeds" in specs
        s = serve_batch_specs(cfg, INPUT_SHAPES["decode_32k"])
        assert s["token"].shape == (128, 1)


def test_sharding_rules():
    """Rule table resolves to the expected Megatron layout (unit-level, no
    devices needed: we check the PartitionSpec assignment logic)."""
    from repro.core.spec import P
    from repro.launch.sharding import _spec_for
    from jax.sharding import PartitionSpec as PS
    # column parallel
    assert _spec_for("dense_blocks/attn/qkv/w", P((36, 2560, 6144), stack=1),
                     16) == PS(None, None, "model")
    # row parallel
    assert _spec_for("dense_blocks/attn/o/w", P((36, 4096, 2560), stack=1),
                     16) == PS(None, "model", None)
    # expert parallel
    assert _spec_for("moe_blocks/moe/w_gu", P((61, 256, 7168, 4096),
                                              stack=2), 16) == \
        PS(None, "model", None, None)
    # non-divisible -> replicate
    assert _spec_for("dense_blocks/attn/qkv/w", P((2, 30, 30), stack=1),
                     16) == PS()
    # norm scales replicate
    assert _spec_for("final_norm/s", P((2560,)), 16) == PS()


@pytest.mark.slow
def test_dryrun_debug_mesh_subprocess():
    """End-to-end: lower+compile a reduced arch on a 4-device mesh in a
    subprocess (train + decode), assert ok status and collective parse."""
    code = (
        "import sys, json\n"
        "from repro.launch.dryrun import run_one\n"
        "r1 = run_one('qwen3-4b', 'train_4k', 'debug', save=False, debug=True)\n"
        "r2 = run_one('rwkv6-7b', 'decode_32k', 'debug', save=False, debug=True)\n"
        "print('RESULT', json.dumps([{k: v for k, v in r.items()"
        " if k in ('status','flops','error')} for r in (r1, r2)]))\n"
    )
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    m = re.search(r"RESULT (.*)", out.stdout)
    rs = json.loads(m.group(1))
    for r in rs:
        assert r["status"] == "ok", r
        assert r["flops"] > 0


@pytest.mark.parametrize("arch", ["tiny", "rwkv6-7b"])
def test_fused_prefill_matches_loop_prefill(arch):
    """serve.py's single-jitted-scan prefill must generate EXACTLY what the
    token-at-a-time reference path does (same cache, same logits), for both
    KV-cache attention and recurrent-state archs."""
    from repro.core.spec import init_params
    from repro.launch.serve import greedy_decode
    from repro.models.transformer import build_model
    cfg = get_config(arch, reduced=(arch != "tiny"))
    model = build_model(cfg)
    params = init_params(model.spec, jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 7), 0,
                                 cfg.vocab_size)
    want = greedy_decode(model, params, prompts, 6, 24, prefill="loop")
    got = greedy_decode(model, params, prompts, 6, 24, prefill="fused")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
