"""programs.compiles_in_window.serve (count): backend compiles (or
persistent-cache retrievals) that `jax.monitoring` reported inside the
window; 0 is what a warmed run shows. Layer: programs. Source: program
counters. Moves serve_tokens_per_s."""


def read(view):
    return len(view.meter.between(*view.window))
