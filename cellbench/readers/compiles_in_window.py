"""Backend compilations JAX reported between the start of the window and
the drain of its last operation (jax.monitoring; a persistent-cache hit
counts too: a shape that was not warmed)."""


def read(cell):
    return cell.compiles_window.get("compiles")
