"""The benchmark of kueue-tpu: data-driven cells run end to end on a TPU.

``BENCHMARK.json`` at the root of the checkout names the cells; the
parts each cell names are files of their own under this directory (see
``benchmark.registry``). ``python3 -m benchmark`` runs one cell once
(see ``benchmark.harness``).
"""
