"""Plain float32 references of the served configurations.

``bench/refs/<config>.py`` holds one configuration's forward pass; this
package's ``common`` module holds the parts they share.  Nothing here
imports the program."""
