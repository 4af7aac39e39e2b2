from repro.kernels.held_experts.ops import held_experts  # noqa: F401
