"""Device time of the two-kind latent decoder's decode module per decode step
it ran: the layers are unrolled in the step's body as the ``sparse_latent_moe``
cell's are (one family), so the reading is that cell's, under a name of this
cell's own (an accepted reader's list of cells gains none)."""

from benchmark.layer_metrics.sparse_latent_decode_step_dev_ms import read  # noqa: F401
