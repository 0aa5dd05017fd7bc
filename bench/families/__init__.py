"""Model families, one module each, found by the configuration file's
``family`` key (``bench/spec.py``). A family module provides:

- ``program_config(c, options)``: the program's ``ModelConfig`` for the
  configuration file ``c``, refused if any width differs from the file;
- ``layout(c)``: name -> (shape, kind, scale) of every weight, the layout
  ``bench.weights.make`` draws; ``stacked(c)``: the names stacked over the
  layers on their first axis; ``to_program(c, w, scanned)`` and
  ``from_program(tree)``: the maps into and out of the program's parameter
  tree;
- the plain float32 reference: ``token_gaps(c, w, tokens, fp8)`` for the
  serve check and ``train(c, make, batches, fp8)`` for the train check
  (built on ``bench.reference``);
- model FLOPs: ``prefill_flops(c, prompt)``, ``decode_flops(c, prompt,
  tokens)``, ``train_token_flops(c, seq)``, and ``kernel_sizes(c)``, the
  sizes a kernel's operands may have been padded from (``bench/kernels``);
- ``smoke(c)``: the keys of ``c`` to change for the CPU tests' small widths.
"""
