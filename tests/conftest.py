# NOTE: no XLA_FLAGS here on purpose -- smoke tests and benches must see the
# real 1-CPU device. Only launch/dryrun.py forces 512 host devices, and only
# in its own process. Multi-device tests spawn subprocesses with the flag.
import jax

jax.config.update("jax_enable_x64", False)
