"""One module per architecture, found by the configuration file's published
``model_type``: ``bench/harness/arch/<model_type>.py``.

A configuration whose architecture is new brings its module as a new file;
the shared harness never names a weight or a published key.  A module
defines:

``program_config(conf) -> ModelConfig``
    the program's config from the file's published keys.
``dims(cfg) -> dict``
    the sizes its ``forward`` and FLOP counts read.
``param_shapes(cfg) -> dict``
    leaf -> ``(shape, fan_in[, dtype])`` in the program's parameter layout;
    ``model.make_params`` draws one key per leaf in flatten order.
``forward(params, tokens, m, quant=False)``
    the plain float32 reference: logits ``(S, vocab)`` at every position of
    ``tokens``; ``quant=True`` is the float8 control.
``prefill_flops(m, seq)``, ``decode_flops(m, position)``
    the required work of a prompt and of one decode step
    (``harness/flops.py`` says what counts).
``tiny(conf, layers, routing)``
    the configuration cut to CPU size for the tests, with ``layers`` layers:
    ``routing="published"`` keeps the published routing, ``"small"`` a
    small one in float32.
"""

from __future__ import annotations

import functools
import importlib.util
import pathlib
import re
from types import ModuleType
from typing import Dict

DIR = pathlib.Path(__file__).resolve().parent


@functools.lru_cache(maxsize=None)
def _load(path: pathlib.Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(f"{__name__}.{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def get(conf: Dict) -> ModuleType:
    """The architecture module of a configuration file; raises
    ``ValueError``, naming the file looked for, where there is none."""
    name = conf.get("model_type", "")
    path = DIR / f"{name}.py"
    if not (re.fullmatch(r"[A-Za-z0-9][A-Za-z0-9_]*", name)
            and path.is_file()):
        raise ValueError(f"no architecture module {path} for model_type "
                         f"{name!r} of configuration {conf.get('name')!r}")
    return _load(path)
