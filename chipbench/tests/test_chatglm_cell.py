"""The ChatGLM3-6B cell's files and its per-layer metric ``prefill_mfu.steady``:
the committed configuration has the widths written out by hand in
``test_costs.py``, the cell loads by name, and the metric reads a value
worked by hand from a synthetic trace, and nothing from spans without
``real``."""
import json
from types import SimpleNamespace

import pytest

from chipbench import prefill_spans, trace_reduce
from chipbench import spec as specs
from chipbench.tests.test_costs import MODELS
from chipbench.tests.tiny import DATA

CELL = "chatglm3-6b.pp2.long-prompt-sat"
# A model whose operations are easy to count: per token 1 x (2 x 2 x (1 + 1
# + 1 + 1) x 2 + 6 x 2 x 1) = 44 dense, 2 x 2 x 3 = 12 in the head, and
# 4 x 1 x 2 = 8 per attended (query, key) pair.
SMALL = {"n_layers": 1, "d_model": 2, "n_heads": 1, "n_kv_heads": 1,
         "head_dim": 2, "d_ff": 1, "vocab_size": 3}
PEAK = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}


def _bench():
    return json.loads((specs.ROOT / "BENCHMARK.json").read_text())


def test_committed_config_has_the_hand_written_widths():
    conf = json.loads(
        (specs.HERE / "configs" / "chatglm3-6b.pp2.json").read_text())
    model = conf["model"]
    assert {k: model[k] for k in MODELS["chatglm3-6b.pp2"]} == \
        MODELS["chatglm3-6b.pp2"]
    assert conf["deployment"] == {"chips": 1, "stages": 2,
                                  "layers_per_chip": 14}
    published = conf["published"]
    assert published["num_layers"] == 2 * model["n_layers"]
    assert (published["hidden_size"], published["ffn_hidden_size"],
            published["multi_query_group_num"], published["kv_channels"],
            published["padded_vocab_size"]) == (
        model["d_model"], model["d_ff"], model["n_kv_heads"],
        model["head_dim"], model["vocab_size"])
    # the reference's RMSNorm epsilon is the published one, and the
    # program's
    from repro.models.registry import get_arch

    assert model["norm_eps"] == published["layernorm_epsilon"] == \
        get_arch("chatglm3-6b").cfg.norm_eps


def test_load_cell_loads_the_chatglm_cell():
    cell = specs.load_cell(_bench(), CELL)
    assert cell.chips == 1
    assert cell.conf["arch"] == "chatglm3-6b"
    assert cell.deploy["attn_chunk"] == 496
    assert cell.deploy["max_prompt_len"] % cell.deploy["attn_chunk"] == 0
    assert cell.mix["prompt_len"]["max"] == cell.deploy["max_prompt_len"]
    assert "ttft_p95_ms" in [m["name"] for m in cell.end_to_end]
    assert "prefill_mfu.steady" in [m["name"] for m in cell.per_layer]
    for name in ("prefill_mfu.steady", "queue_wait_ms.steady",
                 "prefill_pad_share.steady"):
        assert callable(specs.reader(cell, name))


# ------------------------------------------------- a synthetic trace

def _event(name, start_ns, dur_ns, **stats):
    return SimpleNamespace(name=name, start_ns=start_ns, duration_ns=dur_ns,
                           stats=stats)


def _trace(spans, runs):
    """A ``ProfileData`` stand-in: a host plane with ``bench.traced`` over
    [1000, 9000] ns and the given ``engine.prefill`` spans, a device plane
    with the given runs of the prefill program."""
    host = SimpleNamespace(name="/host:CPU", lines=[SimpleNamespace(
        name="python3",
        events=[_event(trace_reduce.SPAN, 1000, 8000)] + spans)])
    dev = SimpleNamespace(name="/device:TPU:0", lines=[SimpleNamespace(
        name="XLA Modules", events=runs)])
    return SimpleNamespace(planes=[host, dev])


def test_prefill_mfu_reads_a_hand_worked_value():
    """Two traced prefills of real prompts 3 and (1, 2) tokens, with runs
    of 1 and 3 ns; a prefill before the span and its run are left out.
    FLOPs: a 3-token prompt 3 x 44 + 8 x 6 + 12 = 192, one of 1 token
    44 + 8 + 12 = 64, one of 2 tokens 88 + 24 + 12 = 124: 380 in all, over
    4 ns at 1e12 FLOP/s, 9.5 %."""
    data = _trace(
        spans=[_event("engine.prefill", 500, 100, real="7", attn_chunk=0),
               _event("engine.prefill", 2000, 100, real="3", attn_chunk=0),
               _event("engine.prefill", 5000, 100, real="1 2",
                      attn_chunk=2)],
        runs=[_event("jit__prefill(11)", 700, 50),
              _event("jit__prefill(11)", 2300, 1),
              _event("jit__paged(12)", 4000, 500),
              _event("jit__prefill(11)", 5200, 3)])
    pre = prefill_spans.prefills(data)
    assert [(p["real"], p["attn_chunk"]) for p in pre] == [([3], 0),
                                                          ([1, 2], 2)]
    assert [p["run_s"] for p in pre] == pytest.approx([1e-9, 3e-9])
    assert prefill_spans.mfu(pre, SMALL, PEAK) == pytest.approx(9.5)


def test_prefill_mfu_reads_nothing_from_spans_without_real():
    """A program older than the spans' ``real`` (synthetic, and a v5e
    trace recorded from one) gives no value."""
    data = _trace(spans=[_event("engine.prefill", 2000, 100, rids=4,
                                shape="2 48")],
                  runs=[_event("jit__prefill(11)", 2300, 1)])
    pre = prefill_spans.prefills(data)
    assert pre[0]["real"] is None and pre[0]["run_s"] == pytest.approx(1e-9)
    assert prefill_spans.mfu(pre, SMALL, PEAK) is None
    chip = prefill_spans.prefills(
        trace_reduce.load(DATA / "tiny_spans.xplane.pb"))
    assert len(chip) == 1 and chip[0]["real"] is None
    assert chip[0]["run_s"] > 0
    assert prefill_spans.mfu(chip, SMALL, PEAK) is None
