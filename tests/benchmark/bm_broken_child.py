"""The training child with its step broken underneath the harness: it
reports its metrics and returns its state unchanged."""

import json
import sys


def unchanged(step):
    import jax
    import jax.numpy as jnp

    def broken(state, batch):
        _, metrics = step(jax.tree.map(jnp.copy, state), batch)
        return state, metrics

    return broken


if __name__ == "__main__":
    from benchmark import train_child

    sys.exit(train_child.run(json.load(open(sys.argv[1])),
                             wrap_step=unchanged))
