"""``harness/moe_scopes.py``: the vocabulary is the program's, the
classifier reads jax's own name forms, and the reduction sums leaf ops
of the traced window per MoE scope."""

from benchmarks.harness import moe_scopes, xplane
from ray_tpu.models import transformer as T


def test_vocabulary_is_the_programs():
    assert moe_scopes.MOE_SCOPES == T.MOE_SCOPES


def test_classify_reads_jaxs_name_forms():
    body = "jit(fused)/jvp()/while/body/closed_call/"
    back = "jit(fused)/transpose(jvp())/while/body/closed_call/checkpoint/"
    assert moe_scopes.classify(body + "mlp/experts/jit(gmm)/pallas_call") == "experts"
    assert moe_scopes.classify(back + "mlp/experts/jit(tgmm)/pallas_call") == "experts"
    assert moe_scopes.classify(back + "rematted_computation/mlp/router/dot_general") == "router"
    assert moe_scopes.classify(body + "mlp/dispatch/sort") == "dispatch"
    assert moe_scopes.classify(body + "mlp/experts/mlp/experts/checkpoint/jit(silu)/mul") == "experts"
    # a dense mlp, another block, a parameter that merely contains the word
    assert moe_scopes.classify(body + "mlp/dot_general") is None
    assert moe_scopes.classify(body + "attention/dot_general") is None
    assert moe_scopes.classify("params['layers']['router']") is None
    assert moe_scopes.classify("") is None and moe_scopes.classify(None) is None


def events(*rows):
    return [xplane.parse(f"%{name} = f32[8]{{0}} fusion(%p)", start, end) for name, start, end in rows]


def test_attribute_sums_leaf_ops_of_the_window_per_scope():
    spans = [xplane.Event("data", 0, 10), xplane.Event("report", 90, 100),
             xplane.Event("data", 100, 110), xplane.Event("report", 190, 200)]
    ops = {0: events(("gmm.1", 10, 40), ("sort.2", 40, 50), ("fusion.3", 50, 60),
                     ("fusion.4", 120, 150), ("fusion.9", 300, 400))}
    names = {
        "gmm.1": "jit(f)/jvp()/while/body/closed_call/mlp/experts/jit(gmm)/pallas_call",
        "sort.2": "jit(f)/jvp()/while/body/closed_call/mlp/dispatch/sort",
        "fusion.3": "jit(f)/jvp()/while/body/closed_call/mlp/router/dot_general",
        "fusion.4": "jit(f)/jvp()/while/body/closed_call/attention/dot_general",
        "fusion.9": "jit(f)/jvp()/while/body/closed_call/mlp/experts/mul",   # after the window
    }
    got = moe_scopes.attribute(ops, spans, names)
    assert got["steps"] == 2
    assert got["scope_s"] == {"router": 10e-9, "dispatch": 10e-9, "experts": 30e-9}
    run = {"moe_scopes": got}
    assert moe_scopes.scope_ms(run, "experts") == 30e-9 / 2 * 1e3
    assert moe_scopes.scope_ms(run, "router", "dispatch") == 20e-9 / 2 * 1e3


def test_a_program_without_the_scopes_has_nothing_to_read():
    spans = [xplane.Event("data", 0, 10), xplane.Event("report", 90, 100)]
    ops = {0: events(("fusion.1", 10, 40))}
    assert moe_scopes.attribute(ops, spans, {"fusion.1": "jit(f)/jvp()/mlp/dot_general"}) is None
    assert moe_scopes.attribute({}, spans, {}) is None
    for run in ({"moe_scopes": None}, {"facts": {"trace": None}}, {}):
        assert moe_scopes.scope_ms(run, "experts") is None
