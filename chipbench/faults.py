"""Faults planted under the timed path, for the check's own tests: each
wraps the program's train step, and the check has to come out false."""
import jax


def unchanged_state(step):
    """A step that returns its state unchanged."""
    def broken(params, opt_state, batch):
        _, _, metrics = step(params, opt_state, batch)
        return params, opt_state, metrics
    return broken


def half_batch(step):
    """Half of the batch left out, the mean taken over the rest."""
    def broken(params, opt_state, batch):
        half = jax.tree.map(lambda x: x[:x.shape[0] // 2], batch)
        return step(params, opt_state, half)
    return broken


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch}
