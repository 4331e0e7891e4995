"""The benchmark's harness: finds a cell's parts by name, makes its inputs,
runs its window, reduces its trace and checks its outputs."""
