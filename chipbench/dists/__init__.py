"""Item distributions beyond the generator's built-in ``zipf`` and
``caida``, one file each: a mix's ``item_dist: "<name>"`` reads
``chipbench/dists/<name>.py``, which supplies

``sample(rng, n, universe, skew)``
    ``n`` item ids in ``[0, universe)`` drawn with the
    ``numpy.random.Generator`` ``rng`` (the mix's ``item_skew`` as
    ``skew``), as an integer array.
"""
