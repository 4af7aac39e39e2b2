"""One driver per kind of configuration, found by the kind's name."""
