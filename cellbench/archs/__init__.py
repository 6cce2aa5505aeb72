"""One module an architecture: ``archs/<model_type>.py``, found by the
``model_type`` key the configuration file took over from its source.

Everything else of ``cellbench`` is free of architecture: the runner of a
decoder-LM training job (``runners/train_lm.py``), the reference's loop over
blocks of rows and Adam steps (``reference.py``), the comparison
(``checks.py``) and the readers ask the configuration's module for what
only an architecture knows.  A later PR adds an architecture as this one
file beside its configuration and its cells, and edits none that is here.

What a module gives (``gpt2.py`` is the example; every function takes the
configuration file's dict as it is, with the source's own key names):

- *reference*, in float32 ``jax.numpy``, importing nothing of the program:
  ``dims(config)`` (at least ``vocab``: the token ids a corpus may draw,
  ``seq``: the positions the configuration holds, ``layers``),
  ``STACKED`` (the names of ``weight_shapes`` whose axis 0 runs over layers),
  ``weight_shapes(config)``, ``init_weights(config, seed_words)``,
  ``leaf_names(config)`` (in the order of ``reference.leaf_norms``) and
  ``loss_and_grads(config, weights, tokens, mode)`` for one block of rows,
  ``mode`` one of ``reference.MODES``;
- *program side* (the only functions that import ``tpudist``, and only
  when called): ``build_module(config, job)``, the module whose ``apply``
  ``make_lm_train_step`` takes; ``program_tree(config, weights)``, the
  reference's weights as that module's parameters; ``named_leaves(config,
  params)``, a program tree's tensors in ``leaf_names`` order;
- *yardstick*: ``train_flops_per_token(config, seq)`` (model FLOPs, by the
  conventions of ``flops.py``) and ``kernel_work(config, per_chip_batch,
  seq)``: for each named kernel of the program that this architecture's
  step runs, ``name -> (operations, bytes)`` its algorithm needs on one
  chip in one step.
"""

from __future__ import annotations

import importlib
from pathlib import Path


def load(config: dict):
    """The module of ``config["model_type"]``; an unknown one is an error
    that names the file to add."""
    model_type = config.get("model_type")
    if not model_type:
        raise LookupError(
            f"configuration {config.get('name')!r} has no model_type: it "
            f"names the architecture's module, cellbench/archs/<model_type>.py")
    name = f"{__name__}.{model_type}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise LookupError(
            f"model_type {model_type!r}: cellbench/archs/{model_type}.py does "
            f"not exist (there are: {known()}); an architecture is added as "
            f"that one file") from None


def known() -> list:
    return sorted(p.stem for d in __path__ for p in Path(d).glob("*.py")
                  if p.stem != "__init__")
