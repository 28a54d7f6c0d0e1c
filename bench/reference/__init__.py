"""Plain references the benchmark compares the program against, one
module a model.

A configuration file (``bench/configs/<name>.json``) names its model in
its ``reference`` key; the harness loads ``bench/reference/<reference>.py``
by that name (``bench.harness.model_of``) and reaches everything of the
model through it.  So a new architecture is new files: its configuration,
its module here, and its traffic, cells and metric readers.  A model
module provides, for a configuration file's dict ``c``:

* ``param_shapes(c)``: a tree of ``(shape, init)`` leaves
  (``core.is_shape``) in the layout of the program's train state, so
  that leaves compare one to one;
* ``init_params(c, key)``: the weights from ``core.seed_key(seed)``,
  made on the device under ``jax.jit``;
* ``nll_sum(params, batch, c, dtype)``: the sum of the negative
  log-likelihood over the positions ``batch["loss_mask"]`` selects, and
  their count; ``dtype`` is the compute precision (float32, or the
  control's lower one);
* ``program_config(c)``: the program's ModelConfig for the file, the
  only function that names the program;
* ``model_flops_per_step(c, B, S)``: the matmul FLOPs the model needs
  for ``B`` sequences of ``S`` tokens, forward and backward, no
  recomputation (``mfu``);
* ``step_matmuls(c, B, S)``: (FLOPs, bytes) of the matmuls one step
  executes, recomputation included (``matmul_roofline``).

Its traffic helpers (an MLM's ``mask_tokens``) stay in it, for the
traffic sources of that model.  ``core`` holds what every model shares:
the key from a seed, gradients in blocks of rows, AdamW and its
schedule, leaf norms and ``follow``.  The reference imports nothing of
the program and takes nothing the program made.
"""
