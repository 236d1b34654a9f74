"""Plain references, one module per configuration's ``reference`` key. They
import nothing of the program and take nothing it has made."""
