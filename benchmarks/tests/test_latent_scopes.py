"""``harness/latent_scopes.py``: the vocabulary is the program's, the
classifier reads jax's own name forms, and the reduction sums leaf ops of
the traced window per scope; a program without the scopes reads None."""

from benchmarks.harness import latent_scopes, xplane
from benchmarks.layer_metrics import mla_proj_ms, shared_expert_ms
from ray_tpu.models import transformer as T


def test_vocabulary_is_the_programs():
    assert latent_scopes.LATENT_SCOPES == T.LATENT_SCOPES


def test_classify_reads_jaxs_name_forms():
    body = "jit(fused)/jvp()/while/body/closed_call/"
    back = "jit(fused)/transpose(jvp())/while/body/closed_call/checkpoint/"
    assert latent_scopes.classify(body + "attention/latent/dot_general") == "latent"
    assert latent_scopes.classify(back + "attention/latent/concatenate") == "latent"
    assert latent_scopes.classify(back + "rematted_computation/attention/latent/checkpoint/mul") == "latent"
    assert latent_scopes.classify(body + "mlp/shared/dot_general") == "shared"
    assert latent_scopes.classify(back + "mlp/shared/checkpoint/jit(silu)/mul") == "shared"
    # W_q / W_o and the kernels, the routed experts, a dense MLP, a parameter's name
    assert latent_scopes.classify(body + "attention/dot_general") is None
    assert latent_scopes.classify(body + "attention/shard_map/jit(_flash_forward)/pallas_call") is None
    assert latent_scopes.classify(body + "mlp/experts/jit(gmm)/pallas_call") is None
    assert latent_scopes.classify(body + "mlp/dot_general") is None
    assert latent_scopes.classify("params['layers']['shared_gate']") is None
    assert latent_scopes.classify("") is None and latent_scopes.classify(None) is None


def events(*rows):
    return [xplane.parse(f"%{name} = f32[8]{{0}} fusion(%p)", start, end) for name, start, end in rows]


def test_attribute_sums_leaf_ops_of_the_window_per_scope():
    spans = [xplane.Event("data", 0, 10), xplane.Event("report", 90, 100),
             xplane.Event("data", 100, 110), xplane.Event("report", 190, 200)]
    ops = {0: events(("fusion.1", 10, 40), ("fusion.2", 40, 50), ("fusion.3", 50, 60),
                     ("fusion.4", 120, 150), ("fusion.9", 300, 400))}
    names = {
        "fusion.1": "jit(f)/jvp()/while/body/closed_call/attention/latent/dot_general",
        "fusion.2": "jit(f)/transpose(jvp())/while/body/closed_call/attention/latent/concatenate",
        "fusion.3": "jit(f)/jvp()/while/body/closed_call/mlp/shared/dot_general",
        "fusion.4": "jit(f)/jvp()/while/body/closed_call/attention/dot_general",
        "fusion.9": "jit(f)/jvp()/while/body/closed_call/mlp/shared/mul",   # after the window
    }
    got = latent_scopes.attribute(ops, spans, names)
    assert got["steps"] == 2
    assert got["scope_s"] == {"latent": 40e-9, "shared": 10e-9}
    run = {"latent_scopes": got}
    assert mla_proj_ms.read(run) == 40e-9 / 2 * 1e3
    assert shared_expert_ms.read(run) == 10e-9 / 2 * 1e3


def test_a_program_without_the_scopes_has_nothing_to_read():
    """What the parent commit, and every other family, gives these
    readers: None, and no exception."""
    spans = [xplane.Event("data", 0, 10), xplane.Event("report", 90, 100)]
    ops = {0: events(("fusion.1", 10, 40))}
    names = {"fusion.1": "jit(f)/jvp()/mlp/experts/dot_general"}
    assert latent_scopes.attribute(ops, spans, names) is None
    assert latent_scopes.attribute({}, spans, {}) is None
    for run in ({"latent_scopes": None}, {"facts": {"trace": None}}, {}):
        assert mla_proj_ms.read(run) is None and shared_expert_ms.read(run) is None
