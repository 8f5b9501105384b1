"""Plain float32 references, one module per kind of model (``bench/kinds``),
named by each configuration's ``kind`` key. A reference imports nothing of
the program and takes nothing it made: it draws its own weights from the
seed, through its kind."""
