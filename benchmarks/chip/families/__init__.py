"""Model families of the benchmark, one module each, named by a
configuration's ``family`` key and loaded from its file by
``harness.load_family``. A family module gives:

- ``model_config(name, cfg)``: the program's ``ModelConfig`` for the
  configuration (the only function that imports the program, inside
  itself);
- ``param_specs(cfg, peft)``: every parameter leaf as
  ``reference.LeafSpec``, sorted by path as the program draws its keys;
- ``reference_model(cfg, peft)``: a ``reference.Model``, its layer groups
  in forward order (each a stack prefix, a layer count and a layer
  function of plain ``jax.numpy``) and its embedding, final norm and
  head leaves;
- ``model_flops_per_token(cfg, seq_len, peft)``: the FLOPs rule of
  ``mfu``.
"""
