"""The dense decoder family: ``models/llama.py`` at a configuration file's
sizes, served by ``serve/llm.py::LlamaGenerator``, checked against
``reference/dense_decoder.py``, counted by ``harness/flops.py``.

A configuration file names its family (``"family": "dense_decoder"``) and
``loader.load_family`` finds this module by that name. It is the one place
of the benchmark that knows the program's names for the family; drivers
and readers ask it and import none of those modules themselves. What a
family module gives (another family brings a module with the same names,
its reference under ``reference/`` and its configuration files, and edits
nothing):

- ``check(m)``: raises, naming the key, for a file this family does not
  understand or the program does not compute;
- ``training(m)``: ``init``, ``logical_axes`` and ``loss`` as
  ``create_train_state`` and ``make_train_step`` take them;
- ``Served`` and ``served_kwargs(m, engine, seed)``: the deployment class
  of the program and how it is constructed from the file. The harness
  asks three things of a served class beyond serving: ``warm_step_programs``,
  ``last_position_logits`` and ``compiled_step_programs`` (below);
- ``REFERENCE``: the module under ``reference/`` whose ``loss`` and
  ``last_logits`` decide ``correct`` (``loader.load_reference``);
- ``num_params``, ``train_flops_per_token``, ``attention_kernel_flops``,
  ``attention_kernel_bytes``: what the readers count with.

Importing this module imports no jax: the harness process and the readers
load it too.
"""

from __future__ import annotations

from typing import Any, Dict, List

from benchmark.harness import flops, modelcfg
from ray_tpu.serve.llm import LlamaGenerator

REFERENCE = "dense_decoder"
num_params = flops.num_params
train_flops_per_token = flops.train_flops_per_token
attention_kernel_flops = flops.flash_train_flops
attention_kernel_bytes = flops.flash_train_bytes
check = modelcfg.check_supported


def training(m: Dict[str, Any]) -> Dict[str, Any]:
    from ray_tpu.models.llama import (
        init_llama, llama_logical_axes, llama_loss)

    cfg = modelcfg.build_llama_config(m)
    return {"init": lambda key: init_llama(cfg, key),
            "logical_axes": llama_logical_axes(cfg),
            "loss": lambda p, b: llama_loss(p, b, cfg)}


def served_kwargs(m: Dict[str, Any], engine: Dict[str, Any],
                  seed: int) -> Dict[str, Any]:
    return dict(
        config=modelcfg.build_llama_config(m),
        lora_rank=engine["lora_rank"],
        max_batch_size=engine["max_batch_size"],
        allowed_batch_sizes=tuple(engine["allowed_batch_sizes"]),
        max_new_tokens=engine["max_new_tokens"],
        seq_bucket=engine["seq_bucket"], seed=seed % (2 ** 31))


class _OwedByTheProgram:
    """What the harness asks of a served class, written against
    ``LlamaGenerator``'s private names because the program does not have
    the methods yet (PERF.md, Open questions). ``Served`` puts the
    program's class first, so a method of one of these names that
    ``LlamaGenerator`` gains takes this one's place with no edit here: a
    step that runs more device programs (a prefill, a decode step, a cache
    update) then warms them itself."""

    def warm_step_programs(self, seq_len: int) -> None:
        """Compile (or find in the cache) and run every device program a
        step runs at one padded length and the engine's batch: today the
        one program of ``_step``, its ids brought to the host."""
        import jax.numpy as jnp
        import numpy as np

        rows = self.engine.max_batch_size
        ids, _ = self._step_fn(
            self._params, jnp.asarray(np.zeros((rows, seq_len), np.int32)),
            None, np.zeros(rows, np.int32))
        np.asarray(ids)

    def last_position_logits(self, prompt: List[int]):
        """``[vocab]`` float32 after the prompt's last token, from the
        weights this replica serves, through the step's program."""
        import jax.numpy as jnp
        import numpy as np

        tokens = np.zeros((self.engine.max_batch_size, len(prompt)), np.int32)
        tokens[0] = prompt
        return np.asarray(self._fwd(self._params, jnp.asarray(tokens),
                                    None))[0, len(prompt) - 1]

    def compiled_step_programs(self) -> int:
        return self._step_fn._cache_size()


class Served(LlamaGenerator, _OwedByTheProgram):
    pass
