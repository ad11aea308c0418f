"""One driver per kind of cell: serving and training."""
