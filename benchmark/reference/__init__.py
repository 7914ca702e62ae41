"""Plain numpy references, one module per script, found by the name a
traffic file gives. They import nothing of the program and take nothing
it has made: only the seeded data and the range's lower end."""
