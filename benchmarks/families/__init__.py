"""Model families: for each architecture the builder that drives the
program, the benchmark's own seeded weights, a plain float32 reference and
the analytic FLOP count. A configuration (``configs/<name>/config.json``)
names its family and gives the sizes."""
