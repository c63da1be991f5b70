"""One module per kind of job a cell can run; a cell names its mode."""
