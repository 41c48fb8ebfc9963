"""Plain references: the models and the federated round in straightforward
``jax.numpy``, written from the published descriptions.  They import
nothing of the program under test and take nothing it has made; the
benchmark hands them the same seeded weights and data it hands the
program."""
