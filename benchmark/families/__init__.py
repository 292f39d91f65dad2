"""The door through which an architecture enters the harness. No JAX here.

A configuration file may say ``"family": "<name>"``; without the key the
family is ``llama_dense``. ``load(config)`` returns the module
``benchmark/families/<name>.py``, which holds or names everything the
harness needs of that architecture: its sizes, the object the program takes
as its configuration, the weights from a seed in the layout the program
runs, the plain float32 reference of one layer, the lower-precision controls
that reference knows, and the least operations and bytes of its work
(``INTERFACE`` below; ``benchmark/README.md`` has one line a function).

The harness keeps what is the same for every architecture: the served class
and the engine path, the trainer child, the scorers' sampling, comparison
and limits, the trace's executable names. A family module imports JAX and
the program only inside its functions: the parent of a run imports it for
``dims`` and the op counts, and never imports JAX.
"""

from __future__ import annotations

import importlib

from benchmark.manifest import NAME

DEFAULT = "llama_dense"
# what a family gives: to every cell, and to a cell of one kind
INTERFACE = {
    None: ("dims", "controls", "layer_kinds", "program_config",
           "reference_globals", "reference_layer", "block", "head"),
    "serve": ("serving_tree", "decode_step_bytes", "prefill_flops"),
    "train": ("training_tree", "program_leaf", "train_flops_per_token"),
}


def load(config: dict, kind: str = None):
    """The family module of a configuration (its JSON, read in), checked
    for what a cell of ``kind`` (``serve``, ``train``) calls."""
    name = config.get("family", DEFAULT)
    if not isinstance(name, str) or not NAME.match(name):
        raise LookupError(f"not a family name: {name!r}")
    try:
        module = importlib.import_module(f"benchmark.families.{name}")
    except ModuleNotFoundError as exc:
        if exc.name != f"benchmark.families.{name}":
            raise
        raise LookupError(
            f"unknown family {name!r}: no benchmark/families/{name}.py")
    missing = [f for f in INTERFACE[None] + INTERFACE.get(kind, ())
               if not callable(getattr(module, f, None))]
    if missing:
        raise LookupError(f"family {name!r} lacks {missing}")
    return module
