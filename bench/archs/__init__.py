"""Model architectures the benchmark's plain reference knows, one module each.

A configuration file names its architecture under ``"arch"``, and
``harness/spec.py`` imports ``archs/<arch>.py`` by its path.  A new
architecture is a new module here; no harness file changes.  Each module
provides:

* ``Dims`` — a frozen dataclass of the sizes the reference and the weights
  read, with the classmethods ``from_config(config)`` (the configuration
  file's ``config`` as run) and ``from_model(cfg)`` (a registry
  ``ModelConfig``); both give equal ``Dims`` for the same model.  Every
  ``Dims`` carries ``layers``, ``heads``, ``kv_heads``, ``head_dim`` and
  ``vocab``, which the shared counts of ``harness/counts.py`` read;
* ``CONFIG_KEYS`` — published ``config.json`` key -> the ``ModelConfig``
  field that holds it, a dotted path for a nested one (``moe.top_k``): the
  registry is checked against each, and a key the file lists under
  ``reduced`` is set through it;
* ``check(cfg, config)`` — raises ``ValueError`` where the registry's
  ``cfg`` is not this architecture or disagrees with ``config`` beyond the
  keys of ``CONFIG_KEYS``;
* ``shapes(d)`` — the weight tree as the engine lays it out: each leaf a
  ``(shape, fan)`` pair for ``harness/weights.make``;
* ``logits(params, tokens, d, fp8=False)`` — the float32 forward pass,
  ``(S,)`` token ids -> ``(S, vocab)`` logits, with every weight matrix
  rounded by ``reference.round_fp8`` when ``fp8`` (the control).
"""
