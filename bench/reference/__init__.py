"""Plain float32 references: one PaME round (Algorithm 1 with the PME
average of Algorithm 2) and each configuration's block as the repository
computes it.  They import nothing from the program."""
