"""Names of the ``jax.named_scope`` regions of the training step.

Each layer of the step enters one of these where its work happens, so
the compiled HLO's ``op_name`` metadata, and with it every device
operation in a profiler trace, names the layer it belongs to.  The
benchmark's per-layer metrics match them by name: rename one here and
in its reader together.  An exchange op runs under ``exchange/<label>``
with the plan op's label (``repro.dist.plan.execute``).
"""

FWD_BWD = "fwd_bwd"        # the model's forward and backward pass
XENT = "xent"              # the chunked cross-entropy and the LM head
COMPRESS = "compress"      # the compressor's local work
SELECT = "select"          # error feedback and top-k selection
AE = "ae"                  # the autoencoder: encode, decode, its update
EXCHANGE = "exchange"      # one plan op's transport call, per label
OPTIMIZER = "optimizer"    # the optimizer's update
