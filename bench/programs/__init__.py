"""Adapters from a configuration file to the program's own model task:
the one place where the benchmark names the program's model code.  One
module per model family, named by the configuration's ``family``."""
