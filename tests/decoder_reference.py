"""How a pattern decoder's test file holds the program to the benchmark's
plain float32 reference (``cellbench/archs/<arch>.py``): the two error
measures, a tiny configuration from ``cellbench/tests/data``, the precision
context, the pair of program and reference on the same weights and tokens,
their logits, three Adam steps through ``make_lm_train_step``, and the
count of dense products in a gradient's jaxpr.  One
decision, written down once; ``tests/test_hybrid.py``, ``test_olmo_hybrid``,
``test_nemotron_h``, ``test_laguna`` and ``test_granite_hybrid`` import it
(``from tests.decoder_reference import ...``, as ``tests/check_failures.py``
is imported), and so does the next decoder's file.

Every program here runs COMPILED, one ``jax.jit`` a call.  Op by op the CPU
compiles a one-op program for each of a decoder's few hundred operations,
which took 4x the time of the one compile (PR 46: a four-layer pair's
``value_and_grad`` 29-33 s eager, 7.5 s jitted) and changes no number the
tolerances see (``tests/test_decoder_reference.py`` holds that).
"""

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from cellbench import reference
from tpudist.models.transformer import lm_loss

#: the benchmark's tiny fixture configurations, one a decoder
DATA = Path(__file__).resolve().parent.parent / "cellbench" / "tests" / "data"


def rel(got, want) -> float:
    """The difference's norm over the wanted tensor's: a gradient's measure."""
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def worst(got, want) -> float:
    """The largest difference over the wanted tensor's largest entry: a
    value's measure."""
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def dense_products(jaxpr) -> int:
    """``dot_general`` equations in ``jaxpr`` and every jaxpr nested in it
    (what a rematerialised layer runs again shows as more of them in the
    gradient's)."""
    found = 0
    for eqn in jaxpr.eqns:
        found += eqn.primitive.name == "dot_general"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += dense_products(sub)
    return found


def tiny(config: dict, dtype="float32", **keys) -> dict:
    """A deep copy of a fixture configuration with ``keys`` overridden, run
    in ``dtype``."""
    config = json.loads(json.dumps(config))
    config.update(keys)
    config["as_run"]["compute_dtype"] = dtype
    return config


def highest():
    """The context every float32 comparison runs in.  The CPU multiplies
    float32 exactly; stated for the reader, and part of a compiled program's
    key, so a fixture of wider scope enters it for itself."""
    return jax.default_matmul_precision("highest")


@functools.lru_cache(maxsize=None)
def _init_weights(arch, config_text: str):
    """ONE compiled ``init_weights`` a configuration: a file's tests seed the
    same few configurations again and again, and a compile is seconds."""
    config = json.loads(config_text)
    return jax.jit(lambda words: arch.init_weights(config, words))


def seeded(arch, config: dict, seed: int) -> dict:
    """The reference's weights from a seed, as the benchmark's runner and
    ``reference.train_readings`` make them: compiled, the seed as data."""
    return _init_weights(arch, json.dumps(config, sort_keys=True))(
        reference.split_seed(seed))


def reference_pair(arch, config: dict, *, seed=7, rows=2, seq=128,
                   options=None) -> dict:
    """Program and reference on the same seeded weights and the same
    ``[rows, seq]`` tokens: each one's loss and every gradient, the
    program's module built with ``options`` (rematerialised as the cells
    are, unless told), and the reference's forward as ONE compiled function
    of the weights, so that every test that asks for its logits shares a
    compile."""
    options = {"remat": "nothing"} if options is None else options
    with highest():
        weights = seeded(arch, config, seed)
        tokens = jax.random.randint(jax.random.PRNGKey(seed), (rows, seq), 0,
                                    config["vocab_size"])
        module = arch.build_module(config, options)
        params = arch.program_tree(config, weights)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: lm_loss(module.apply(p, tokens), tokens)))(params)
        ref_loss, ref_grads = jax.jit(
            lambda w: arch.loss_and_grads(config, w, tokens))(weights)
    return dict(config=config, weights=weights, tokens=tokens, module=module,
                params=params, loss=loss, grads=grads, ref_loss=ref_loss,
                ref_grads=ref_grads, reference_forward=jax.jit(
                    lambda w: arch.forward(config, w, tokens)))


def logits(pair: dict, module=None, params=None) -> jax.Array:
    """The program's logits on the pair's tokens; of another ``module`` (an
    arm changed) or on other ``params`` where given."""
    module = pair["module"] if module is None else module
    return jax.jit(module.apply)(
        pair["params"] if params is None else params, pair["tokens"])


def reference_logits(pair: dict, weights=None) -> jax.Array:
    """The reference's logits on the pair's tokens, on other ``weights``
    where given."""
    return pair["reference_forward"](
        pair["weights"] if weights is None else weights)


def run_steps(arch, config: dict, module, weights, batches, lr, aux=False):
    """Adam at ``lr`` over ``batches`` through ``make_lm_train_step`` from
    the reference's ``weights``: the state behind the last step, each
    step's loss and, of a step built with ``aux``, what the last one said
    (else None)."""
    import optax

    from tpudist.runtime.mesh import MeshConfig, make_mesh
    from tpudist.train import init_lm_state, make_lm_train_step

    tx = optax.adam(lr)
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    # the state shares the reference's weights: nothing is donated
    step = make_lm_train_step(module.apply, tx, mesh, donate_state=False,
                              aux=aux)
    state = init_lm_state(arch.program_tree(config, weights), tx)
    losses, said = [], None
    for batch in batches:
        state, loss, *said = step(state, jnp.asarray(batch))
        losses.append(float(loss))
    return state, losses, said[0] if said else None
