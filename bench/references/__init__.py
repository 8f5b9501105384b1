"""Plain float32 references, one module per kind of model, named by each
configuration's ``reference`` key. A reference imports nothing of the
program and takes nothing it made: it draws its own weights from the seed
(``bench.weights``)."""
