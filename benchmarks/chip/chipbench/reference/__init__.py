"""Plain float32 references, independent of the program.

A configuration file names its reference by module: ``"reference": "decoder"``
is ``chipbench/reference/decoder.py``. :func:`load` imports it, and
``spec.resolve`` calls :func:`load`, so a configuration whose reference is
missing fails there, before any run. A new architecture brings its reference
as a new module here and names it in its configuration file; the drivers take
whatever module the configuration names.

A reference module gives, for training cells (``train_driver``), the
functions :data:`INTERFACE` lists under ``train``:

- ``init_weights(cfg, seed)``: the weights the program's jitted init makes
  from the seed, in the module's own layout;
- ``leaf_paths(cfg)``: ``(path, shape, fan_in)`` of each of the program's
  leaves, by ``jax.tree_util.keystr``, in the order the program flattens its
  parameters;
- ``draw_leaf(cfg, seed, index)``: leaf ``index`` of ``leaf_paths`` as the
  program's init draws it;
- ``leaf_norms(tree, cfg)``: ``{path: L2 norm}`` of a tree in the module's
  layout, per program leaf;
- ``change_norms(params, cfg, seed)``: ``{path: L2 norm}`` of the weights'
  change from their seeded start, per program leaf;
- ``loss_and_grads(params, tokens, labels, cfg, numerics, denom=, acc=)``:
  a block of rows' share of the mean token loss over ``denom`` tokens, and
  the float32 gradients added into ``acc`` (the previous block's);
- ``adamw_step(params, m, v, grads, count, traffic)``: ``(params, m, v,
  clip scale)``;

and for serving cells (``serve_driver``), those under ``serve``:

- ``init_weights(cfg, seed)``;
- ``logits(params, tokens, cfg, numerics=)``: ``(B, S, padded vocab)``
  float32.

``numerics`` is ``"f32"`` (float32 at ``Precision.HIGHEST``) or ``"fp8"``
(every matrix product's operands rounded to float8 e4m3), which the controls
use. ``common.py`` holds what does not depend on the model: the matrix
product, the e4m3 rounding, the seeded draw, paths into nested weights, and
AdamW.
"""

from __future__ import annotations

import importlib


# The functions a reference module gives, by the traffic kind of its cells.
INTERFACE = {
    "train": ("init_weights", "leaf_paths", "draw_leaf", "leaf_norms", "change_norms",
              "loss_and_grads", "adamw_step"),
    "serve": ("init_weights", "logits"),
}


class MissingReference(LookupError):
    pass


def load(config: dict):
    """The reference module that ``config["reference"]`` names."""
    name = config.get("reference")
    where = f"configuration {config.get('name')!r}"
    if not isinstance(name, str) or not name.isidentifier():
        raise MissingReference(f"{where} names no reference module: 'reference' is {name!r}")
    module = f"{__name__}.{name}"
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise MissingReference(f"{where} names reference {name!r}, and there is no module "
                               f"{module}") from None
