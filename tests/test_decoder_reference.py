"""``tests/decoder_reference.py`` runs every program compiled, where the
decoders' test files once called them op by op.  This is the one place that
says the change of dispatch changed no number: on one architecture (granite,
the cheapest) the harness's logits are the eager calls' own; and that a
configuration handed to a test is the test's own copy."""

import json

from cellbench.archs import granitemoehybrid as arch
from tests.decoder_reference import (DATA, highest, logits, reference_logits,
                                     reference_pair, tiny, worst)

TINY = json.loads((DATA / "tiny-granite-hybrid.json").read_text())


def test_a_tiny_configuration_is_the_tests_own_copy():
    """Five files' tests share each fixture configuration through ``tiny``:
    what one of them overrides or writes into its copy (``as_run`` is
    nested) reaches neither the fixture nor the next copy."""
    before = json.dumps(TINY, sort_keys=True)
    config = tiny(TINY, "bfloat16", num_hidden_layers=2)
    assert (config["num_hidden_layers"], config["as_run"]["compute_dtype"]) == (
        2, "bfloat16")
    config["as_run"]["router_trained"] = False
    config["layer_types"].append("attention")
    assert json.dumps(TINY, sort_keys=True) == before
    again = tiny(TINY)
    assert again["as_run"]["compute_dtype"] == "float32"
    assert "router_trained" not in again["as_run"]
    assert again["layer_types"] == TINY["layer_types"]
    assert again["num_hidden_layers"] == TINY["num_hidden_layers"]


def test_compiled_and_eager_dispatch_agree():
    """Program and reference, each through one ``jax.jit`` and each op by
    op: within 1e-6 of the logits' largest entry (the tolerances the pair
    is held to start at 1e-5).  One row of 64 tokens: the eager calls are
    what this file's time is."""
    config = tiny(TINY)
    pair = reference_pair(arch, config, rows=1, seq=64)
    with highest():
        eager = pair["module"].apply(pair["params"], pair["tokens"])
        assert worst(logits(pair), eager) < 1e-6
        eager = arch.forward(config, pair["weights"], pair["tokens"])
        assert worst(reference_logits(pair), eager) < 1e-6
    # and they are two computations, not one tensor compared with itself
    assert 0 < worst(logits(pair), reference_logits(pair)) < 3e-5
