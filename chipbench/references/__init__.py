"""Plain references of the served semantics, one file per kind
(``chipbench/kinds/``). Each imports nothing of the program."""
