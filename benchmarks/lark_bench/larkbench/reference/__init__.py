"""Plain references of the engines' semantics; they import nothing
of the program."""
